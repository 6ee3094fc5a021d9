"""Device time a Bellman step of the critic's update: the self time of
the operations under the scope `backward` (the critic's forward and
backward pass through `torso` and `q_head`, the loss inside it) in the
whole executions of the K-step program (device trace;
`device_scopes.py`)."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(run, ("backward",))
