"""Share of the rows `utils/native.gather_rows` gathered over the run
that the native library served, from the program's two counters
`native.gather_rows.native_rows` and `.fallback_rows`: under 100 where
the library did not build or an array was not contiguous."""


def read(run):
  from tensor2robot_tpu import telemetry

  rows = telemetry.registry().scalars("native.gather_rows.")
  native = rows.get("native.gather_rows.native_rows", 0.0)
  total = native + rows.get("native.gather_rows.fallback_rows", 0.0)
  return 100.0 * native / total if total else None
