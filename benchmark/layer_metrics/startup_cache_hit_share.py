"""Share of the start's compile requests that the persistent cache
answered, from the gauges `startup.cache_hits` and
`startup.cache_misses` (the counters `compile_cache.hits` and `.misses`
from the construction of the train loop to its first log): 100 is a
warm start, anything less names a run whose `setup_s` holds a compile.
None where the program sets neither gauge or the cache saw no
request."""


def read(run):
  from tensor2robot_tpu import telemetry

  gauges = telemetry.registry().scalars("startup.cache_")
  hits = gauges.get("startup.cache_hits", 0.0)
  total = hits + gauges.get("startup.cache_misses", 0.0)
  return 100.0 * hits / total if total else None
