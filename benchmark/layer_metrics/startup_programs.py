"""Programs the backend was asked for (compiled, or read from the
persistent cache) between the construction of the train loop and its
first log, from the gauge `startup.programs` (the counter
`compile_cache.backend_compiles` over that stretch). None where the
program sets no such gauge."""


def read(run):
  from tensor2robot_tpu import telemetry

  return telemetry.registry().scalars("startup.").get(
      "startup.programs")
