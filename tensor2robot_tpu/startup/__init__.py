"""Cold-start elimination: persistent compile cache + overlapped startup.

The north-star fleet restarts constantly — preemptible TPU workers,
rolling predictor updates (Podracer, arXiv:2104.06272, makes
preemption-tolerance a first-class property) — yet a process start
serially pays trace + XLA compile + orbax restore + input-pipeline
spin-up. This package makes restarts cheap and measured:

  * `compile_cache` — gin-configurable wiring of jax's persistent XLA
    compilation cache (`jax_compilation_cache_dir` + min-entry knobs),
    shared by the trainer, predictors and the serving engine,
    plus `CompileWatch`: a jax.monitoring tap that counts cache
    hits/misses so "the warm path compiled nothing" is provable.
  * `orchestrator` — `run_overlapped`: named startup phases on threads
    (device compile, disk restore, host input prep don't contend),
    with per-phase wall timings and the serial-vs-overlapped saving.

What a start costs is measured inside the real trainer: `setup_s` and
the seven `startup_*` metrics of `BENCHMARK.json` (PERF.md section 3).
"""

from tensor2robot_tpu.startup.compile_cache import (
    CompileWatch,
    aval_of,
    cache_entry_count,
    configure_compilation_cache,
)
from tensor2robot_tpu.startup.orchestrator import (
    StartupReport,
    run_overlapped,
)
