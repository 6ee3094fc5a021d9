"""Records a tiny profiler trace on whatever device JAX has and prints
its planes, lines and first events: the recorded trace that
benchmark/tests checks the reduction against was made with this."""
import os
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
  os.makedirs(out_dir, exist_ok=True)
  print("devices", jax.devices(), flush=True)

  @jax.jit
  def prog(x):
    def body(c, _):
      c = jnp.tanh(c @ c) * 0.5
      return c, jnp.sum(c)
    c, s = jax.lax.scan(body, x, None, length=4)
    return c, s

  x = jnp.ones((512, 512), jnp.bfloat16)
  jax.block_until_ready(prog(x))
  jax.profiler.start_trace(out_dir)
  for _ in range(3):
    c, s = prog(x)
    jax.block_until_ready(c)
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
  main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_probe")
