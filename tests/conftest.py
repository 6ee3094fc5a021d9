"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding is tested without TPU hardware by splitting the host
CPU into 8 virtual XLA devices (SURVEY.md §5 lesson: add the multi-chip
tests the reference lacked). Must run before jax initializes its backends.
"""

import atexit
import os
import shutil
import tempfile

# Overwrite, not setdefault: tests always run on the virtual CPU mesh,
# whatever platform the environment selects.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
  os.environ["XLA_FLAGS"] = (
      xla_flags + " --xla_force_host_platform_device_count=8").strip()
# The suite pins the cache-placement contract for the UNSET case; an
# ambient cache dir would also make explicit `cache_dir=` args inert.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

# Keep TF (used only for TFRecord IO / jax2tf export) off any accelerator.
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

# Belt and braces: jax may already be imported (pytest plugin autoload),
# in which case the env var was read too early. The config update works
# as long as no backend has been initialized yet.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", None)

# The test process's persistent compile cache lives in a per-session
# scratch dir, not the checkout's `.jax_cache`: results must not depend
# on what an earlier session compiled.
from tensor2robot_tpu.startup import compile_cache  # noqa: E402

compile_cache.DEFAULT_CACHE_DIR = tempfile.mkdtemp(prefix="t2r_test_cache_")
atexit.register(shutil.rmtree, compile_cache.DEFAULT_CACHE_DIR,
                ignore_errors=True)
