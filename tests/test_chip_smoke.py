"""chip_smoke.py's off-chip contract (the on-chip half is the driver's).

Without its rehearsal switch the smoke must not pass on a machine
where JAX finds no TPU: non-zero exit and no verdict line. The
`--rehearse-cpu` run itself is the builder's pre-flight, not a tier-1
test (three child processes and four compiles).
"""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_fails_without_a_tpu(tmp_path):
  env = dict(os.environ)
  env["JAX_PLATFORMS"] = "cpu"
  out = subprocess.run(
      [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"),
       f"--model-dir={tmp_path / 'run'}"],
      env=env, capture_output=True, text=True, timeout=600)
  assert out.returncode != 0, out.stdout[-2000:]
  assert '"ok"' not in out.stdout
  # The refusal is JAX's own: the children name the TPU explicitly.
  assert "Unable to initialize backend 'tpu'" in out.stderr


def test_fails_without_the_package(tmp_path):
  """A directory that holds chip_smoke.py and nothing else of the repo."""
  lone = tmp_path / "chip_smoke.py"
  lone.write_bytes(
      open(os.path.join(REPO_ROOT, "chip_smoke.py"), "rb").read())
  env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
  out = subprocess.run([sys.executable, str(lone)], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True,
                       timeout=120)
  assert out.returncode != 0
  assert '"ok"' not in out.stdout
