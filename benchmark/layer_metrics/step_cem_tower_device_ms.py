"""Device time a Bellman step of the CEM target's action tower: the
self time of the operations under the scope `cem_tower` (the int8 head
convolutions over the population of candidate actions) in the whole
executions of the K-step program (device trace; `device_scopes.py`)."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(run, ("cem_tower",))
