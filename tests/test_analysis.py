"""Tests for the t2rcheck static-analysis suite (ISSUE 5).

Every rule ID gets a POSITIVE fixture (a snippet that must trigger it)
and a NEGATIVE fixture (the corrected form that must not), plus the
mechanics every rule shares: inline pragmas, the baseline ledger, the
CLI exit-code contract, the no-jax-import invariant of the AST path,
and the tier-1 guarantee that every shipped .gin config validates.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tensor2robot_tpu.analysis import findings as findings_lib
from tensor2robot_tpu.analysis.concurrency_rules import (
    run_concurrency_rules,
)
from tensor2robot_tpu.analysis.findings import (
    Baseline,
    Finding,
    PragmaIndex,
    RULE_CATALOG,
)
from tensor2robot_tpu.analysis.fleet_rules import run_fleet_rules
from tensor2robot_tpu.analysis.import_rules import (
    import_closure,
    run_import_rules,
)
from tensor2robot_tpu.analysis.jax_rules import run_jax_rules
from tensor2robot_tpu.analysis.spmd_rules import (
    ENTRY_BINARY,
    run_spmd_rules,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, name, code):
  path = tmp_path / name
  path.write_text(textwrap.dedent(code))
  return str(path)


def _rules(found):
  return {f.rule for f in found}


# ---------------------------------------------------------------------------
# JAX tracing-hazard rules
# ---------------------------------------------------------------------------

class TestJaxRules:

  def test_jax201_host_sync_positive(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import jax

        @jax.jit
        def step(state, batch):
          out = state + batch
          jax.block_until_ready(out)
          loss = out.sum().item()
          return loss
    """)
    found = run_jax_rules([str(tmp_path)], str(tmp_path))
    assert "JAX201" in _rules(found)
    assert sum(f.rule == "JAX201" for f in found) == 2

  def test_jax201_float_on_traced_arg(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import jax

        @jax.jit
        def step(x):
          return float(x) + 1.0
    """)
    assert "JAX201" in _rules(
        run_jax_rules([str(tmp_path)], str(tmp_path)))

  def test_jax201_negative_outside_trace(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import jax

        def host_loop(state):
          jax.block_until_ready(state)  # fine: not traced
          return state
    """)
    assert _rules(run_jax_rules([str(tmp_path)], str(tmp_path))) == set()

  def test_jax202_impure_calls_positive(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import time
        import numpy as np
        import jax

        @jax.jit
        def step(x):
          print("stepping")
          t = time.time()
          noise = np.random.normal(size=3)
          return x + noise.sum() + t
    """)
    found = run_jax_rules([str(tmp_path)], str(tmp_path))
    assert sum(f.rule == "JAX202" for f in found) == 3

  def test_jax202_reaches_transitive_callee(self, tmp_path):
    # The hazard hides one call deep: reachability must follow it.
    _write(tmp_path, "mod.py", """
        import time
        import jax

        def helper(x):
          return x * time.time()

        @jax.jit
        def step(x):
          return helper(x)
    """)
    found = run_jax_rules([str(tmp_path)], str(tmp_path))
    assert any(f.rule == "JAX202" and f.scope == "helper"
               for f in found)

  def test_jax202_negative_pure(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
          return jnp.sum(x ** 2)
    """)
    assert _rules(run_jax_rules([str(tmp_path)], str(tmp_path))) == set()

  def test_jax203_tracer_branch_positive(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import jax

        @jax.jit
        def step(x, loss):
          if loss > 0:
            x = x * 2
          return x
    """)
    assert "JAX203" in _rules(
        run_jax_rules([str(tmp_path)], str(tmp_path)))

  def test_jax203_negative_static_idioms(self, tmp_path):
    # None-checks, bare-container truthiness and raise-guards are the
    # trace-time-static idioms the rule documents as excluded.
    _write(tmp_path, "mod.py", """
        import jax

        @jax.jit
        def step(x, rng, batch_stats, block):
          if rng is None:
            rng = 0
          if batch_stats:
            x = x + 1
          if block % 2:
            raise ValueError("bad block")
          return x
    """)
    assert _rules(run_jax_rules([str(tmp_path)], str(tmp_path))) == set()

  def test_jax204_global_mutation_positive(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import jax

        COUNT = 0

        @jax.jit
        def step(x):
          global COUNT
          COUNT += 1
          return x
    """)
    assert "JAX204" in _rules(
        run_jax_rules([str(tmp_path)], str(tmp_path)))

  def test_pallas_kernel_is_device_code_not_host_sync(self, tmp_path):
    """The Pallas carve-outs (ISSUE 7): pl.load/pl.store/ref indexing
    and Python branches on static block params inside a kernel are
    device code — zero findings, zero pragmas."""
    _write(tmp_path, "mod.py", """
        import functools
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref, *, block: int):
          if block > 4:  # static block-param branch: the kernel idiom
            val = pl.load(x_ref, (slice(None),))
          else:
            val = x_ref[...]
          pl.store(o_ref, (slice(None),), val * 2)

        def run(x):
          kernel = functools.partial(_kernel, block=8)
          return pl.pallas_call(kernel, out_shape=None)(x)
    """)
    assert _rules(run_jax_rules([str(tmp_path)], str(tmp_path))) == set()

  def test_pallas_kernel_still_scanned_for_impurity(self, tmp_path):
    """Pallas-aware ≠ pallas-blind: kernels ARE traced device code,
    so a genuine hazard inside one (host clock) is still flagged —
    through both the direct-name and the partial-variable entry."""
    _write(tmp_path, "mod.py", """
        import functools
        import time
        from jax.experimental import pallas as pl

        def _bad_kernel(x_ref, o_ref):
          time.sleep(0.1)
          o_ref[...] = x_ref[...]

        def run(x):
          return pl.pallas_call(_bad_kernel, out_shape=None)(x)

        def _bad_kernel2(x_ref, o_ref, *, n: int):
          t = time.time()
          o_ref[...] = x_ref[...] + t

        def run2(x):
          kernel = functools.partial(_bad_kernel2, n=4)
          return pl.pallas_call(kernel, out_shape=None)(x)
    """)
    found = run_jax_rules([str(tmp_path)], str(tmp_path))
    assert sum(f.rule == "JAX202" for f in found) == 2
    assert {f.scope for f in found} == {"_bad_kernel", "_bad_kernel2"}

  def test_pallas_partial_vars_resolve_per_scope(self, tmp_path):
    """Two functions both naming their partial `kernel` must resolve
    to their OWN kernels — a module-wide name map would let the
    second shadow the first and miss its hazard."""
    _write(tmp_path, "mod.py", """
        import functools
        import time
        from jax.experimental import pallas as pl

        def _hazard_kernel(x_ref, o_ref, *, n: int):
          time.sleep(0.1)
          o_ref[...] = x_ref[...]

        def _clean_kernel(x_ref, o_ref, *, n: int):
          o_ref[...] = x_ref[...]

        def run_hazard(x):
          kernel = functools.partial(_hazard_kernel, n=2)
          return pl.pallas_call(kernel, out_shape=None)(x)

        def run_clean(x):
          kernel = functools.partial(_clean_kernel, n=2)
          return pl.pallas_call(kernel, out_shape=None)(x)
    """)
    found = run_jax_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["JAX202"]
    assert found[0].scope == "_hazard_kernel"

  def test_entry_detection_call_form_and_scan(self, tmp_path):
    # jax.jit(fn) / jax.lax.scan(body, ...) call forms, not decorators.
    _write(tmp_path, "mod.py", """
        import time
        import jax

        def body(carry, x):
          time.sleep(0.1)
          return carry, x

        def train():
          return jax.lax.scan(body, 0, None, length=3)

        def step(x):
          return x * time.time()

        jitted = jax.jit(step)
    """)
    found = run_jax_rules([str(tmp_path)], str(tmp_path))
    scopes = {f.scope for f in found if f.rule == "JAX202"}
    assert scopes == {"body", "step"}


# ---------------------------------------------------------------------------
# Concurrency & lifecycle rules
# ---------------------------------------------------------------------------

class TestConcurrencyRules:

  def test_con301_blocking_under_lock_positive(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import subprocess
        import threading
        import time


        class Worker:

          def __init__(self):
            self._lock = threading.Lock()

          def slow(self):
            with self._lock:
              time.sleep(1.0)
              subprocess.run(["ls"])
              with open("/tmp/x") as f:
                return f.read()
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert sum(f.rule == "CON301" for f in found) == 3

  def test_con301_negative_outside_lock(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import threading
        import time


        class Worker:

          def __init__(self):
            self._lock = threading.Lock()
            self._value = 0

          def ok(self):
            with self._lock:
              self._value += 1
            time.sleep(1.0)  # after release: fine
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON301" not in _rules(found)

  def test_con301_untimed_queue_get_under_lock(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import queue
        import threading


        class Pipe:

          def __init__(self):
            self._lock = threading.Lock()
            self._queue = queue.Queue(maxsize=4)

          def bad(self):
            with self._lock:
              return self._queue.get()
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON301" in _rules(found)

  def test_con302_untimed_get_positive_and_fixed_negative(
      self, tmp_path):
    _write(tmp_path, "mod.py", """
        import queue


        class Consumer:

          def __init__(self):
            self._queue = queue.Queue(maxsize=2)

          def bad(self):
            return self._queue.get()

          def good(self):
            while True:
              try:
                return self._queue.get(timeout=0.1)
              except queue.Empty:
                continue

          def also_good(self):
            return self._queue.get_nowait()
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    con302 = [f for f in found if f.rule == "CON302"]
    assert len(con302) == 1 and con302[0].scope == "Consumer.bad"

  def test_con302_put_on_unbounded_queue_is_fine(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import queue


        class Producer:

          def __init__(self):
            self._queue = queue.Queue()   # unbounded: put never blocks

          def ok(self, item):
            self._queue.put(item)
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON302" not in _rules(found)

  def test_con302_put_on_bounded_queue_flags(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import queue


        class Producer:

          def __init__(self):
            self._queue = queue.Queue(maxsize=2)

          def bad(self, item):
            self._queue.put(item)
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON302" in _rules(found)

  def test_con303_lock_order_cycle_positive(self, tmp_path):
    _write(tmp_path, "a_mod.py", """
        import threading


        class Store:

          def __init__(self):
            self._alock = threading.Lock()
            self._block = threading.Lock()

          def forward(self):
            with self._alock:
              with self._block:
                return 1

          def backward(self):
            with self._block:
              with self._alock:
                return 2
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON303" in _rules(found)

  def test_con303_cross_function_cycle_via_calls(self, tmp_path):
    # f holds A and calls g (acquires B); h holds B and calls k
    # (acquires A): the interprocedural edge set must close the cycle.
    _write(tmp_path, "mod.py", """
        import threading


        class Split:

          def __init__(self):
            self._alock = threading.Lock()
            self._block = threading.Lock()

          def take_b(self):
            with self._block:
              return 1

          def take_a(self):
            with self._alock:
              return 2

          def f(self):
            with self._alock:
              return self.take_b()

          def h(self):
            with self._block:
              return self.take_a()
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON303" in _rules(found)

  def test_con303_cycle_through_lock_free_intermediate(self, tmp_path):
    # f holds A → g (NO lock) → h acquires B; reverse path closes the
    # cycle. The eventual-acquires fixpoint must cross the lock-free
    # hop g (code-review regression).
    _write(tmp_path, "mod.py", """
        import threading


        class Hops:

          def __init__(self):
            self._alock = threading.Lock()
            self._block = threading.Lock()

          def h_takes_b(self):
            with self._block:
              return 1

          def g_lockfree(self):
            return self.h_takes_b()

          def f(self):
            with self._alock:
              return self.g_lockfree()

          def reverse(self):
            with self._block:
              with self._alock:
                return 2
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON303" in _rules(found)

  def test_con303_multi_item_with_orders_locks(self, tmp_path):
    # `with A, B:` acquires in item order — it must contribute the
    # A->B edge so the reverse nesting elsewhere closes a cycle
    # (code-review regression).
    _write(tmp_path, "mod.py", """
        import threading


        class Combined:

          def __init__(self):
            self._alock = threading.Lock()
            self._block = threading.Lock()

          def both_at_once(self):
            with self._alock, self._block:
              return 1

          def reverse(self):
            with self._block:
              with self._alock:
                return 2
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON303" in _rules(found)

  def test_con301_re_compile_under_lock_not_flagged(self, tmp_path):
    # `.compile` only blocks when the receiver is a jit/AOT object;
    # a regex compile under a lock is microseconds (code-review
    # regression). The jitted form must still flag.
    _write(tmp_path, "mod.py", """
        import re
        import threading


        class Patterns:

          def __init__(self):
            self._lock = threading.Lock()
            self._jitted = None

          def ok(self, expr):
            with self._lock:
              return re.compile(expr)

          def bad(self, args):
            with self._lock:
              return self._jitted.lower(args).compile()
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    con301 = [f for f in found if f.rule == "CON301"]
    assert [f.scope for f in con301] == ["Patterns.bad"], con301

  def test_con303_negative_consistent_order(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import threading


        class Store:

          def __init__(self):
            self._alock = threading.Lock()
            self._block = threading.Lock()

          def one(self):
            with self._alock:
              with self._block:
                return 1

          def two(self):
            with self._alock:
              with self._block:
                return 2
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON303" not in _rules(found)

  def test_con304_leaked_resource_positive(self, tmp_path):
    _write(tmp_path, "mod.py", """
        from multiprocessing import shared_memory


        def leaky(n):
          shm = shared_memory.SharedMemory(create=True, size=n)
          return shm.name   # the handle is dropped: nothing can close
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON304" in _rules(found)

  def test_con304_class_without_teardown_positive(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import subprocess


        class Launcher:

          def __init__(self):
            self._proc = subprocess.Popen(["sleep", "100"])
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON304" in _rules(found)

  def test_con304_negative_finally_and_teardown(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import subprocess
        from multiprocessing import shared_memory


        class Launcher:

          def __init__(self):
            self._proc = subprocess.Popen(["sleep", "100"])

          def close(self):
            self._proc.terminate()


        def careful(n):
          shm = shared_memory.SharedMemory(create=True, size=n)
          try:
            return bytes(shm.buf[:4])
          finally:
            shm.close()
            shm.unlink()


        def transfer(n):
          shm = shared_memory.SharedMemory(create=True, size=n)
          return shm   # ownership moves to the caller
    """)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    assert "CON304" not in _rules(found)


# ---------------------------------------------------------------------------
# Import hygiene
# ---------------------------------------------------------------------------

class TestImportRules:

  def test_imp401_clean_on_this_repo(self):
    assert run_import_rules(REPO_ROOT) == []

  def test_imp401_positive_on_seeded_tree(self, tmp_path):
    pkg = tmp_path / "tensor2robot_tpu"
    (pkg / "data").mkdir(parents=True)
    (pkg / "config").mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "config" / "__init__.py").write_text("")
    (pkg / "config" / "ginlite.py").write_text("x = 1\n")
    (pkg / "data" / "__init__.py").write_text("")
    (pkg / "data" / "shm_ring.py").write_text("import numpy\n")
    # plane -> helper -> jax: a TRANSITIVE reach, two hops deep.
    (pkg / "data" / "plane.py").write_text(
        "from tensor2robot_tpu.data import helper\n")
    (pkg / "data" / "helper.py").write_text("import jax\n")
    found = run_import_rules(str(tmp_path))
    assert [f.rule for f in found] == ["IMP401"]
    assert "tensor2robot_tpu.data.helper" in found[0].message

  def test_import_closure_computed_from_entry_binary(self):
    # The entry binary's spawn closure is COMPUTED, not enumerated:
    # the module whose jnp constant broke PR 19's fleet spawn is in
    # it, and so is everything the closure walks through — a new
    # module joining the entry import graph is covered automatically.
    closure = import_closure(ENTRY_BINARY, REPO_ROOT)
    assert "tensor2robot_tpu.train_eval" in closure
    assert ("tensor2robot_tpu.preprocessors.image_transformations"
            in closure)
    assert "tensor2robot_tpu" in closure  # ancestor packages execute

  def test_import_closure_empty_off_repo(self, tmp_path):
    # Fixture trees must not inherit repo facts.
    assert import_closure(ENTRY_BINARY, str(tmp_path)) == set()


# ---------------------------------------------------------------------------
# Fleet RPC wire contract: FLT501/FLT502 (ISSUE 20)
# ---------------------------------------------------------------------------

class TestFleetRules:

  DISPATCHER = """
      DISCONNECT_METHOD = "__disconnect__"


      class Handler:

        def handle(self, method, payload, ctx):
          if method == "ping":
            return 1
          if method in ("alpha", "beta"):
            return 2
          if method == DISCONNECT_METHOD:
            return None
          raise ValueError(method)
  """

  def test_flt501_unhandled_method(self, tmp_path):
    _write(tmp_path, "mod.py", self.DISPATCHER + """
      def go(client):
        client.call("pong", {})
        client.call_once("alpha")
        client.call("ping")
        client.call("beta")
    """)
    found = run_fleet_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["FLT501"]
    assert "'pong'" in found[0].message
    assert found[0].scope == "go"

  def test_flt501_negative_all_handled(self, tmp_path):
    _write(tmp_path, "mod.py", self.DISPATCHER + """
      def go(client):
        client.call("ping")
        client.call_once("alpha", {})
        client.call("beta")
    """)
    assert run_fleet_rules([str(tmp_path)], str(tmp_path)) == []

  def test_flt501_literal_through_forwarder(self, tmp_path):
    # The orchestrator pattern: `_aux_call(entry, "m", ...)` forwards
    # its method parameter into `client.call` — literals at the
    # forwarder's call sites are wire sends.
    _write(tmp_path, "mod.py", self.DISPATCHER + """
      class Fleet:

        def _aux_call(self, entry, method, payload=None):
          client = self._clients[entry["name"]]
          return client.call(method, payload)

        def go(self, entry):
          self._aux_call(entry, "ping")
          self._aux_call(entry, "tpyo")
          self._aux_call(entry, "alpha")
          self._aux_call(entry, "beta")
    """)
    found = run_fleet_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["FLT501"]
    assert "'tpyo'" in found[0].message

  def test_flt502_dead_handler_and_disconnect_exempt(self, tmp_path):
    _write(tmp_path, "mod.py", self.DISPATCHER + """
      def go(client):
        client.call("ping")
        client.call("alpha")
    """)
    found = run_fleet_rules([str(tmp_path)], str(tmp_path))
    # "beta" is handled but never sent; the server-synthesized
    # disconnect method must NOT count as dead.
    assert [f.rule for f in found] == ["FLT502"]
    assert "'beta'" in found[0].message
    assert found[0].scope == "Handler.handle"

  def test_silent_without_dispatchers_in_scope(self, tmp_path):
    # A --paths subset with no handle() in sight must not spray
    # FLT501 over every send.
    _write(tmp_path, "mod.py", """
        def go(client):
          client.call("anything", {})
    """)
    assert run_fleet_rules([str(tmp_path)], str(tmp_path)) == []

  def test_silent_without_sends_in_scope(self, tmp_path):
    # ...and a handler-only scope must not report every arm dead.
    _write(tmp_path, "mod.py", self.DISPATCHER)
    assert run_fleet_rules([str(tmp_path)], str(tmp_path)) == []

  def test_repo_wire_contract_closes(self):
    # The live contract: every literal send in fleet/ + serving/
    # resolves against the dispatcher union, and no arm is dead —
    # with zero pragmas.
    found = run_fleet_rules(
        [os.path.join(REPO_ROOT, "tensor2robot_tpu/fleet"),
         os.path.join(REPO_ROOT, "tensor2robot_tpu/serving")],
        REPO_ROOT)
    assert found == []


# ---------------------------------------------------------------------------
# Distributed SPMD correctness: SPMD601/JAX205 (ISSUE 20)
# ---------------------------------------------------------------------------

class TestSpmdRules:

  def test_spmd601_chief_gated_save_transitive(self, tmp_path):
    # The reverted PR-19 bug form: a chief-gated call reaching the
    # orbax writer's collective save one hop down — rank 0 wedges in
    # `sync_global_processes` while peers train on.
    _write(tmp_path, "bug.py", """
        import jax

        def _flush(writer, state):
          writer.save(0, state)

        def train(writer, state):
          if jax.process_index() == 0:
            _flush(writer, state)
    """)
    found = run_spmd_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["SPMD601"]
    assert "writer.save" in found[0].message
    assert found[0].scope == "train"

  def test_spmd601_direct_collective_under_assigned_gate(
      self, tmp_path):
    # `chief = jax.process_index() == 0` makes `chief` a gate name;
    # the collective sits directly in the gated branch.
    _write(tmp_path, "bug.py", """
        import jax
        from jax.experimental import multihost_utils

        def train(state):
          flag = jax.process_index() == 0
          if flag:
            multihost_utils.sync_global_processes("save")
    """)
    found = run_spmd_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["SPMD601"]
    assert "sync_global_processes" in found[0].message

  def test_spmd601_negative_every_rank_saves(self, tmp_path):
    # HEAD's corrected pattern: the save is unconditional, the chief
    # gate guards only host-side logging.
    _write(tmp_path, "good.py", """
        import jax

        def train(writer, logger, state, step):
          chief = jax.process_index() == 0
          if chief:
            logger.write("train", step)
          writer.save(step, state)
          writer.close()
    """)
    assert run_spmd_rules([str(tmp_path)], str(tmp_path)) == []

  def test_spmd601_rank_raise_guard_clean(self, tmp_path):
    _write(tmp_path, "mod.py", """
        def plan(rank, world_size):
          if not 0 <= rank < world_size:
            raise ValueError(f"bad rank {rank}")
          return {"role": "learner" if rank == 0 else "peer"}
    """)
    assert run_spmd_rules([str(tmp_path)], str(tmp_path)) == []

  def test_train_qtopt_head_clean_with_zero_pragmas(self):
    # The acceptance pin: the every-rank-calls-save loop passes the
    # rule on merit, not via suppression.
    path = os.path.join(
        REPO_ROOT, "tensor2robot_tpu/research/qtopt/train_qtopt.py")
    assert run_spmd_rules([path], REPO_ROOT) == []
    with open(path, encoding="utf-8") as f:
      assert "disable=SPMD601" not in f.read()

  def test_jax205_module_level_jnp_constant(self, tmp_path):
    _write(tmp_path, "consts.py", """
        import jax.numpy as jnp

        YIQ = jnp.array([[0.299, 0.587, 0.114]])
    """)
    found = run_spmd_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["JAX205"]
    assert "jnp.array" in found[0].message

  def test_jax205_transitive_module_level_call(self, tmp_path):
    _write(tmp_path, "table.py", """
        import jax.numpy as jnp

        def _build():
          return jnp.eye(3)

        TABLE = _build()
    """)
    found = run_spmd_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["JAX205"]
    assert "_build" in found[0].message

  def test_jax205_negatives(self, tmp_path):
    # All the module-level shapes that must NOT flag: numpy
    # constants, jnp inside functions, pytree registration, config
    # flips, lazy jit wrapping, and the __main__ guard (spawn
    # children import under __mp_main__, so it never runs).
    _write(tmp_path, "ok.py", """
        import jax
        import jax.numpy as jnp
        import numpy as np

        RGB = np.array([1.0, 2.0])
        jax.tree_util.register_pytree_node(dict, id, id)
        jax.config.update("jax_enable_x64", False)

        def compute(x):
          return jnp.asarray(x)

        compute_fast = jax.jit(compute)

        if __name__ == "__main__":
          print(compute(jnp.ones(2)))
    """)
    assert run_spmd_rules([str(tmp_path)], str(tmp_path)) == []

  def test_jax205_entry_closure_escalation(self, tmp_path):
    # A seeded tree with its own entry binary: the hazard module is
    # in the computed spawn closure, so the finding carries the
    # jax.distributed escalation.
    pkg = tmp_path / "tensor2robot_tpu"
    (pkg / "bin").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "bin" / "__init__.py").write_text("")
    (pkg / "bin" / "run_t2r_trainer.py").write_text(
        "from tensor2robot_tpu import consts\n")
    (pkg / "consts.py").write_text(
        "import jax.numpy as jnp\nYIQ = jnp.array([1.0])\n")
    found = run_spmd_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["JAX205"]
    assert "spawn import closure" in found[0].message

  def test_repo_spmd_clean(self):
    # The whole package passes both rules with the baseline EMPTY.
    found = run_spmd_rules(
        [os.path.join(REPO_ROOT, "tensor2robot_tpu")], REPO_ROOT)
    assert found == []

  def test_pragma_suppresses_new_families(self, tmp_path):
    _write(tmp_path, "mod.py", """
        import jax.numpy as jnp

        # count-gated uniform branch, documented:
        # t2rcheck: disable=JAX205
        YIQ = jnp.array([1.0])
    """)
    found = run_spmd_rules([str(tmp_path)], str(tmp_path))
    active, suppressed = findings_lib.apply_pragmas(
        found, str(tmp_path))
    assert active == [] and [f.rule for f in suppressed] == ["JAX205"]

  def test_fingerprints_survive_witness_line_motion(self):
    # Witness chains embed "line N of file" — the fingerprint
    # normalizer must strip the digits so baselines survive motion.
    a = Finding("SPMD601", "a.py", 9, "train",
                "reaches `writer.save` (line 5 of a.py)")
    b = Finding("SPMD601", "a.py", 40, "train",
                "reaches `writer.save` (line 88 of a.py)")
    assert a.fingerprint() == b.fingerprint()

  def test_cli_json_carries_new_rule_ids(self, tmp_path):
    _write(tmp_path, "bad.py", """
        import jax

        DISCONNECT_METHOD = "__disconnect__"

        class H:
          def handle(self, method, payload, ctx):
            if method == "ping":
              return 1
            raise ValueError(method)

        def go(client):
          client.call("pong")

        def train(writer, state):
          if jax.process_index() == 0:
            writer.save(0, state)
    """)
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.analysis",
         "--checks", "fleet,spmd", "--paths", str(tmp_path),
         "--root", str(tmp_path), "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert result.returncode == 1, result.stdout + result.stderr
    payload = json.loads(result.stdout)
    rules = {f["rule"] for f in payload["new"]}
    assert {"FLT501", "FLT502", "SPMD601"} <= rules

  def test_new_families_in_defaults_and_catalog(self):
    from tensor2robot_tpu.analysis import cli

    parser = cli.build_parser()
    defaults = parser.get_default("checks")
    assert "fleet" in defaults and "spmd" in defaults
    assert cli._FLEET_PATHS == ("tensor2robot_tpu/fleet",
                                "tensor2robot_tpu/serving")
    for rule in ("FLT501", "FLT502", "SPMD601", "JAX205"):
      assert rule in RULE_CATALOG
    assert "fleet" in findings_lib.FAMILIES
    assert "spmd" in findings_lib.FAMILIES


# ---------------------------------------------------------------------------
# Pragmas + baseline mechanics
# ---------------------------------------------------------------------------

class TestSuppression:

  def test_inline_pragma_same_line_and_line_above(self):
    index = PragmaIndex(textwrap.dedent("""
        x = 1
        y = queue.get()  # t2rcheck: disable=CON302
        # t2rcheck: disable=JAX201,JAX202
        z = arr.item()
    """))
    assert index.suppresses("CON302", 3)
    assert index.suppresses("JAX201", 5)
    assert index.suppresses("JAX202", 5)
    assert not index.suppresses("CON302", 5)
    assert not index.suppresses("CON302", 2)

  def test_file_level_pragma(self):
    index = PragmaIndex("# t2rcheck: disable-file=CON301\ncode = 1\n")
    assert index.suppresses("CON301", 999)
    assert not index.suppresses("CON302", 999)

  def test_pragma_suppresses_end_to_end(self, tmp_path):
    code = """
        import queue


        class Consumer:

          def __init__(self):
            self._queue = queue.Queue(maxsize=2)

          def blocking_by_design(self):
            # callers own the liveness contract here
            # t2rcheck: disable=CON302
            return self._queue.get()
    """
    _write(tmp_path, "mod.py", code)
    found = run_concurrency_rules([str(tmp_path)], str(tmp_path))
    active, suppressed = findings_lib.apply_pragmas(
        found, str(tmp_path))
    assert active == [] and len(suppressed) == 1

  def test_fingerprint_survives_line_motion(self):
    a = Finding("CON302", "x/y.py", 10, "C.m", "blocking get")
    b = Finding("CON302", "x/y.py", 99, "C.m", "blocking get")
    c = Finding("CON302", "x/OTHER.py", 10, "C.m", "blocking get")
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != c.fingerprint()

  def test_baseline_roundtrip_and_split(self, tmp_path):
    old = Finding("CON302", "a.py", 5, "f", "legacy debt")
    new = Finding("CON301", "b.py", 9, "g", "fresh bug")
    path = str(tmp_path / "baseline.json")
    Baseline().write(path, [old])
    loaded = Baseline.load(path)
    fresh, known = loaded.split([old, new])
    assert [f.rule for f in fresh] == ["CON301"]
    assert [f.rule for f in known] == ["CON302"]

  def test_committed_baseline_is_empty(self):
    # The zero-findings contract of ISSUE 5: debt never accumulates
    # silently — the committed ledger stays empty.
    baseline = Baseline.load(
        os.path.join(REPO_ROOT, findings_lib.DEFAULT_BASELINE))
    assert baseline.fingerprints == set()


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

class TestCli:

  def test_ast_path_never_imports_jax_and_repo_is_clean(self):
    # BOTH halves of the lint.sh stage-1 contract in one subprocess:
    # the repo lints clean, and linting it did not import jax.
    code = (
        "import sys\n"
        "from tensor2robot_tpu.analysis.cli import main\n"
        "rc = main(['--checks', 'jax,concurrency,imports,obs,"
        "fleet,spmd'])\n"
        "assert 'jax' not in sys.modules, 'AST path imported jax'\n"
        "sys.exit(rc)\n")
    result = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stdout + result.stderr

  def test_cli_exits_nonzero_on_seeded_violation(self, tmp_path):
    _write(tmp_path, "bad.py", """
        import queue


        class Consumer:

          def __init__(self):
            self._queue = queue.Queue(maxsize=2)

          def bad(self):
            return self._queue.get()
    """)
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.analysis",
         "--checks", "concurrency", "--paths", str(tmp_path),
         "--root", str(tmp_path)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert result.returncode == 1, result.stdout + result.stderr
    assert "CON302" in result.stdout

  def test_cli_exits_nonzero_on_seeded_jax_violation(self, tmp_path):
    _write(tmp_path, "bad.py", """
        import time
        import jax

        @jax.jit
        def step(x):
          return x * time.time()
    """)
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.analysis",
         "--checks", "jax", "--paths", str(tmp_path),
         "--root", str(tmp_path)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert result.returncode == 1, result.stdout + result.stderr
    assert "JAX202" in result.stdout

  def test_cli_exits_nonzero_on_seeded_import_violation(self, tmp_path):
    pkg = tmp_path / "tensor2robot_tpu"
    for sub in ("data", "config"):
      (pkg / sub).mkdir(parents=True)
      (pkg / sub / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "config" / "ginlite.py").write_text("x = 1\n")
    (pkg / "data" / "shm_ring.py").write_text("import jax\n")
    (pkg / "data" / "plane.py").write_text("")
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.analysis",
         "--checks", "imports", "--root", str(tmp_path)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert result.returncode == 1, result.stdout + result.stderr
    assert "IMP401" in result.stdout

  def test_cli_json_output(self, tmp_path):
    _write(tmp_path, "bad.py", """
        import queue
        q = queue.Queue(maxsize=1)
        item = q.get()
    """)
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.analysis",
         "--checks", "concurrency", "--paths", str(tmp_path),
         "--root", str(tmp_path), "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    payload = json.loads(result.stdout)
    assert result.returncode == 1
    assert payload["new"][0]["rule"] == "CON302"

  def test_write_baseline_then_clean(self, tmp_path):
    _write(tmp_path, "bad.py", """
        import queue
        q = queue.Queue(maxsize=1)
        item = q.get()
    """)
    baseline = str(tmp_path / "baseline.json")
    common = [sys.executable, "-m", "tensor2robot_tpu.analysis",
              "--checks", "concurrency", "--paths", str(tmp_path),
              "--root", str(tmp_path), "--baseline", baseline]
    first = subprocess.run(common + ["--write-baseline"],
                           cwd=REPO_ROOT, capture_output=True,
                           text=True, timeout=120)
    assert first.returncode == 0, first.stdout + first.stderr
    second = subprocess.run(common, cwd=REPO_ROOT,
                            capture_output=True, text=True,
                            timeout=120)
    assert second.returncode == 0, second.stdout + second.stderr
    assert "1 baselined" in second.stdout

  def test_list_rules_covers_catalog(self):
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.analysis",
         "--list-rules"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert result.returncode == 0
    for rule in RULE_CATALOG:
      assert rule in result.stdout


# ---------------------------------------------------------------------------
# Observability hygiene: OBS501 metric-catalog lint (ISSUE 15)
# ---------------------------------------------------------------------------

class TestObsRules:

  CATALOG = """\
  # catalog fixture
  | `replay.adds` | counter | rows |
  | `fleet.rpc.{timeouts,retries}` | counter | ledger |
  | `serving.<tenant>.request_ms` | histogram | latency |
  | prose mentioning a bare `<rest>` placeholder |
  """

  def _run(self, tmp_path, code, catalog=None):
    from tensor2robot_tpu.analysis.obs_rules import run_obs_rules
    _write(tmp_path, "mod.py", code)
    catalog_path = _write(tmp_path, "CATALOG.md",
                          catalog if catalog is not None
                          else self.CATALOG)
    return run_obs_rules([str(tmp_path / "mod.py")], str(tmp_path),
                         catalog_path=catalog_path)

  def test_undocumented_literal_positive(self, tmp_path):
    found = self._run(tmp_path, """
        from tensor2robot_tpu.telemetry import metrics as tmetrics
        tmetrics.counter("replay.undocumented_total").inc()
        """)
    assert _rules(found) == {"OBS501"}
    assert "replay.undocumented_total" in found[0].message

  def test_documented_brace_and_placeholder_negative(self, tmp_path):
    found = self._run(tmp_path, """
        from tensor2robot_tpu.telemetry import metrics as tmetrics
        tmetrics.counter("replay.adds").inc()
        tmetrics.counter("fleet.rpc.retries").inc()
        tmetrics.histogram("serving.tenant_a.request_ms").observe(1.0)
        """)
    assert found == [], [f.render() for f in found]

  def test_bare_placeholder_never_blinds_the_rule(self, tmp_path):
    # The fixture catalog contains a bare `<rest>` in prose; it must
    # NOT compile into a match-everything wildcard.
    found = self._run(tmp_path, """
        from tensor2robot_tpu.telemetry import metrics as tmetrics
        tmetrics.gauge("anything.at_all").set(1.0)
        """)
    assert _rules(found) == {"OBS501"}

  def test_undotted_helper_strings_ignored(self, tmp_path):
    found = self._run(tmp_path, """
        class Thing:
          def counter(self, name):
            return name
        Thing().counter("not_a_metric")
        """)
    assert found == []

  def test_missing_catalog_is_a_finding(self, tmp_path):
    from tensor2robot_tpu.analysis.obs_rules import run_obs_rules
    _write(tmp_path, "mod.py", "x = 1\n")
    found = run_obs_rules([str(tmp_path)], str(tmp_path),
                          catalog_path=str(tmp_path / "missing.md"))
    assert _rules(found) == {"OBS501"}
    assert "catalog missing" in found[0].message

  def test_repo_is_clean(self):
    # The shipped contract: every literal metric name in the package
    # is documented in docs/OBSERVABILITY.md (baseline stays EMPTY).
    from tensor2robot_tpu.analysis.obs_rules import run_obs_rules
    package = os.path.join(REPO_ROOT, "tensor2robot_tpu")
    found = run_obs_rules([package], REPO_ROOT)
    assert found == [], [f.render() for f in found]


# ---------------------------------------------------------------------------
# Gin static validation (imports the framework: the one heavy family)
# ---------------------------------------------------------------------------

class TestGinValidation:

  def test_all_shipped_configs_validate(self):
    # The tier-1 guarantee of ISSUE 5: every shipped experiment config
    # resolves every binding/ref/macro against real signatures.
    from tensor2robot_tpu.analysis.gin_check import (
        discover_configs,
        run_gin_rules,
    )
    package = os.path.join(REPO_ROOT, "tensor2robot_tpu")
    configs = discover_configs([package])
    assert len(configs) == 24, configs  # re-pin when shipping new ones
    found = run_gin_rules([package], REPO_ROOT)
    assert found == [], [f.render() for f in found]

  def test_typoed_param_rejected(self, tmp_path):
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules
    (tmp_path / "typo.gin").write_text(
        "PoseEnvRegressionModel.image_sie = 64\n")
    found = run_gin_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["GIN102"]
    assert "image_sie" in found[0].message

  def test_kwargs_forwarding_follows_mro(self, tmp_path):
    # The param must be accepted when ANY class up the chain takes it
    # (kwargs forwarding) and rejected when none does.
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules
    (tmp_path / "mro.gin").write_text(
        "PoseEnvRegressionModel.aux_loss_weight = 0.5\n")
    assert run_gin_rules([str(tmp_path)], str(tmp_path)) == []

  def test_unknown_configurable_and_ref(self, tmp_path):
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules
    (tmp_path / "unknown.gin").write_text(
        "NoSuchThing.param = 1\n"
        "train_eval_model.model = @AlsoMissing()\n")
    rules = {f.rule for f in
             run_gin_rules([str(tmp_path)], str(tmp_path))}
    assert rules == {"GIN101", "GIN104"}

  def test_dangling_macro_and_defined_macro(self, tmp_path):
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules
    (tmp_path / "macros.gin").write_text(
        "BATCH = 64\n"
        "train_eval_model.batch_size = %BATCH\n"
        "train_eval_model.eval_steps = %MISSING\n")
    found = run_gin_rules([str(tmp_path)], str(tmp_path))
    assert [f.rule for f in found] == ["GIN103"]
    assert "MISSING" in found[0].message

  def test_denylisted_param_and_parse_error(self, tmp_path):
    from tensor2robot_tpu import config as gin
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules

    @gin.configurable("analysis_denylist_probe", denylist=["secret"])
    def probe(secret=1, ok=2):  # noqa: F841 - registered, not called
      return secret, ok

    (tmp_path / "deny.gin").write_text(
        "analysis_denylist_probe.secret = 3\n"
        "analysis_denylist_probe.ok = 4\n"
        "???not a gin statement\n")
    rules = [f.rule for f in
             run_gin_rules([str(tmp_path)], str(tmp_path))]
    assert "GIN105" in rules, rules   # denylisted `secret`
    assert "GIN107" in rules, rules   # the unparseable line
    assert len(rules) == 2, rules     # `ok` binds cleanly

  def test_missing_include_flagged(self, tmp_path):
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules
    (tmp_path / "inc.gin").write_text("include 'nope/missing.gin'\n")
    assert [f.rule for f in
            run_gin_rules([str(tmp_path)], str(tmp_path))] == ["GIN106"]

  def test_include_closure_defines_macros(self, tmp_path):
    # A macro defined in an INCLUDED file resolves for the includer —
    # gin's call-time macro semantics, order-free.
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules
    (tmp_path / "base.gin").write_text("BATCH = 32\n")
    (tmp_path / "top.gin").write_text(
        "train_eval_model.batch_size = %BATCH\n"
        f"include '{tmp_path / 'base.gin'}'\n")
    found = [f for f in run_gin_rules([str(tmp_path)], str(tmp_path))]
    assert found == [], [f.render() for f in found]

  def test_validation_does_not_mutate_registry(self):
    from tensor2robot_tpu import config as gin
    from tensor2robot_tpu.analysis.gin_check import validate_config_file
    gin.clear_config()
    config = os.path.join(
        REPO_ROOT, "tensor2robot_tpu", "research", "pose_env",
        "configs", "train_pose_env.gin")
    validate_config_file(config, REPO_ROOT)
    assert gin.config_str() == ""  # validate-only: no bindings landed


class TestShardingRulesCoverage:
  """GIN108 (ISSUE 12): every sharding rules table matches every
  param of its model family — unmatched-param and dead-regex
  findings; the shipped tables stay clean (baseline stays empty)."""

  def test_repo_family_tables_produce_no_findings(self):
    from tensor2robot_tpu.analysis.gin_check import (
        run_sharding_rules_checks,
    )
    found = run_sharding_rules_checks()
    assert found == [], [f.render() for f in found]

  def test_unmatched_param_flagged(self):
    import numpy as np
    from tensor2robot_tpu.analysis.gin_check import (
        run_sharding_rules_checks,
    )
    from tensor2robot_tpu.parallel import Replicate
    families = {"fixture": (
        ((r"/kernel$", Replicate()),),
        [{"layer": {"kernel": np.zeros((4,)),
                    "bias": np.zeros((4,))}}])}
    found = run_sharding_rules_checks(families)
    assert [f.rule for f in found] == ["GIN108"]
    assert "layer/bias" in found[0].message
    assert "matches no sharding rule" in found[0].message

  def test_dead_regex_flagged(self):
    import numpy as np
    from tensor2robot_tpu.analysis.gin_check import (
        run_sharding_rules_checks,
    )
    from tensor2robot_tpu.parallel import Replicate, ShardLargest
    families = {"fixture": (
        ((r"/stale_name$", ShardLargest()),
         (r".*", Replicate())),
        [{"layer": {"kernel": np.zeros((4,))}}])}
    found = run_sharding_rules_checks(families)
    assert [f.rule for f in found] == ["GIN108"]
    assert "stale_name" in found[0].message
    assert "dead regex" in found[0].message

  def test_final_catchall_default_is_exempt(self):
    """A fully-covering table keeps its safety-net default without a
    dead-regex finding — only NON-final dead rules flag."""
    import numpy as np
    from tensor2robot_tpu.analysis.gin_check import (
        run_sharding_rules_checks,
    )
    from tensor2robot_tpu.parallel import Replicate, ShardLargest
    families = {"fixture": (
        ((r"/kernel$", ShardLargest()),
         (r".*", Replicate())),
        [{"layer": {"kernel": np.zeros((4,))}}])}
    assert run_sharding_rules_checks(families) == []

  def test_broken_template_does_not_blind_other_families(self,
                                                         monkeypatch):
    """One family whose template construction fails must report ITS
    finding and still surface coverage findings for the others."""
    import numpy as np
    from tensor2robot_tpu.analysis.gin_check import (
        run_sharding_rules_checks,
    )
    from tensor2robot_tpu.parallel import Replicate, rules as rules_lib

    fake_rules = {"broken": ((r".*", Replicate()),),
                  "gappy": ((r"/kernel$", Replicate()),)}
    monkeypatch.setattr(rules_lib, "FAMILY_RULES", fake_rules)
    monkeypatch.setattr(rules_lib, "family_rules",
                        lambda name: fake_rules[name])

    def templates(name):
      if name == "broken":
        raise ImportError("no such module")
      return [{"layer": {"kernel": np.zeros((4,)),
                         "bias": np.zeros((4,))}}]

    monkeypatch.setattr(rules_lib, "family_param_templates", templates)
    found = run_sharding_rules_checks()
    assert [f.rule for f in found] == ["GIN108", "GIN108"]
    assert "template construction failed" in found[0].message
    assert "layer/bias" in found[1].message  # 'gappy' still checked

  def test_gin_family_runs_the_coverage_check(self, tmp_path):
    """GIN108 rides `run_gin_rules` — the lint entry point scripts/
    lint.sh and tier-1 invoke."""
    from tensor2robot_tpu.analysis.gin_check import run_gin_rules
    found = run_gin_rules([str(tmp_path)], str(tmp_path))
    assert [f for f in found if f.rule == "GIN108"] == []
