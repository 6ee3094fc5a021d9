import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

import pytest


@pytest.fixture(autouse=True)
def _no_gin_bindings_between_tests():
  """A configuration's gin bindings are process-wide; a test that
  builds a learner from one must not size the next test's model."""
  from tensor2robot_tpu import config as gin
  gin.clear_config()
  yield
  gin.clear_config()
