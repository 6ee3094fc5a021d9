"""Seconds jax spent tracing, lowering and compiling (on a cache hit:
reading and deserialising) between the construction of the train loop
and its first log, on every thread: work, not wall. The sum of the
spans `jit.trace`, `jit.lower` and `jit.compile` of that stretch, from
the gauge `startup.jit_s` (counters `compile.trace_s`, `.lower_s`,
`.backend_s`). None where the program sets no such gauge."""


def read(run):
  from tensor2robot_tpu import telemetry

  return telemetry.registry().scalars("startup.").get("startup.jit_s")
