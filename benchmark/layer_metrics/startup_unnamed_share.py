"""Share of the seconds from the construction of the train loop to the
return of its first jitted call that no top-level `startup.*` span of
the trainer's thread covers, from the gauges `startup.unnamed_s` and
`startup.to_first_enqueue_s`. None where the program sets neither."""


def read(run):
  from tensor2robot_tpu import telemetry

  gauges = telemetry.registry().scalars("startup.")
  total = gauges.get("startup.to_first_enqueue_s")
  if not total:
    return None
  return 100.0 * gauges.get("startup.unnamed_s", 0.0) / total
