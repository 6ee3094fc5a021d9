"""The program's whole share of `setup_s` up to its first step: seconds
from the construction of the train loop (the trainer's entry, the start
of its `startup.services` span) to the first dispatch's results on the
host (the end of the first `loop.log_sync`; the instant
`startup.first_metrics`), from the gauge `startup.to_first_metrics_s`
that the loop sets once, at its first log. None where the program sets
no such gauge."""


def read(run):
  from tensor2robot_tpu import telemetry

  return telemetry.registry().scalars("startup.").get(
      "startup.to_first_metrics_s")
