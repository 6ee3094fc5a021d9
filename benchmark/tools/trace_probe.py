"""Records a tiny profiler trace on whatever device JAX has and prints
its planes, lines and first events: the recorded traces that
benchmark/tests checks the reduction against were made with this.

  python3 benchmark/tools/trace_probe.py <out_dir>           a jitted
      4-iteration scan, three executions with 20 ms of sleep between
      them (tests/data/v5e_scan4.xplane.pb, PR 23)
  python3 benchmark/tools/trace_probe.py <out_dir> scopes    a gradient
      step through two `jax.named_scope`s under one `jax.checkpoint`,
      the first around the program's flash kernel: what the reducer's
      scopes, passes and kernels are read from
      (tests/data/v5e_scopes.xplane.pb, PR 40)
"""
import os
import sys
import time

import jax
import jax.numpy as jnp


def scan_prog():
  @jax.jit
  def prog(x):
    def body(c, _):
      c = jnp.tanh(c @ c) * 0.5
      return c, jnp.sum(c)
    c, s = jax.lax.scan(body, x, None, length=4)
    return c, s

  return prog, (jnp.ones((512, 512), jnp.bfloat16),)


def scopes_prog():
  """`jit_scoped_step`: one block of two scopes, `mla/attend` (a
  projection and the flash kernel on 1 row of 256 positions, 2 heads
  of 128) and `dense_ffn`, under `jax.checkpoint`, and its gradient:
  the kernel's forward program runs on the way forward and again as a
  recomputation, its two backward programs once."""
  from tensor2robot_tpu.ops.flash_attention import flash_attention

  t, h, d = 256, 2, 128
  interpret = jax.default_backend() != "tpu"

  def block(x, w):
    with jax.named_scope("mla/attend"):
      q = (x @ w).reshape(1, t, h, d)
      a = flash_attention(q, q, q, causal=True, interpret=interpret)
    with jax.named_scope("dense_ffn"):
      return x + jnp.tanh(a.reshape(t, h * d) @ w)

  @jax.jit
  def scoped_step(w, x):
    def loss(w):
      y = jax.checkpoint(block)(x, w)
      return jnp.sum(jnp.square(y.astype(jnp.float32)))
    return w - 1e-3 * jax.grad(loss)(w)

  key = jax.random.PRNGKey(0)
  w = jax.random.normal(key, (h * d, h * d), jnp.bfloat16) * 0.05
  return scoped_step, (w, jax.random.normal(key, (t, h * d),
                                            jnp.bfloat16))


def main(out_dir: str, which: str = "scan") -> None:
  os.makedirs(out_dir, exist_ok=True)
  print("devices", jax.devices(), flush=True)
  prog, args = {"scan": scan_prog, "scopes": scopes_prog}[which]()
  jax.block_until_ready(prog(*args))
  options = jax.profiler.ProfileOptions()
  if which == "scopes":  # the device's planes alone: a file to store
    options.python_tracer_level = 0
    options.host_tracer_level = 0
  jax.profiler.start_trace(out_dir, profiler_options=options)
  for _ in range(3):
    jax.block_until_ready(prog(*args))
    time.sleep(0.02)
  jax.profiler.stop_trace()
  path = None
  for root, _, files in os.walk(out_dir):
    for f in files:
      if f.endswith(".xplane.pb"):
        path = os.path.join(root, f)
  print("trace", path, os.path.getsize(path))
  data = jax.profiler.ProfileData.from_file(path)
  for plane in data.planes:
    print("PLANE", repr(plane.name))
    for line in plane.lines:
      events = list(line.events)
      print("  LINE", repr(line.name), len(events))
      for ev in events[:6]:
        stats = {k: v for k, v in list(ev.stats)[:6]}
        print("    ", repr(ev.name)[:80], ev.start_ns, ev.duration_ns,
              stats)
  for d in jax.devices():
    print("mem", d.memory_stats())
  print("key", jax.random.PRNGKey(2**31 + 12345))


if __name__ == "__main__":
  sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
      os.path.abspath(__file__)))))
  main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_probe",
       *sys.argv[2:3])
