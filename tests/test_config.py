"""Tests for the ginlite config engine."""

import pytest

from tensor2robot_tpu import config as gin


@pytest.fixture(autouse=True)
def clean():
  gin.clear_config()
  yield
  gin.clear_config()


@gin.configurable
def make_widget(size=1, color="red", factory=None):
  return {"size": size, "color": color, "factory": factory}


@gin.configurable
def make_gadget(widget=None, scale=1.0):
  return {"widget": widget, "scale": scale}


@gin.configurable
class Engine:

  def __init__(self, power=10, name="eng"):
    self.power = power
    self.name = name


@gin.configurable
def needs_binding(value=gin.REQUIRED):
  return value


class TestBindings:

  def test_simple_binding(self):
    gin.parse_config("make_widget.size = 5")
    assert make_widget()["size"] == 5

  def test_explicit_arg_wins(self):
    gin.parse_config("make_widget.size = 5")
    assert make_widget(size=9)["size"] == 9

  def test_module_qualified(self):
    gin.parse_config("test_config.make_widget.color = 'blue'")
    assert make_widget()["color"] == "blue"

  def test_class_configurable(self):
    gin.parse_config("Engine.power = 99")
    e = Engine()
    assert e.power == 99 and e.name == "eng"
    assert isinstance(e, Engine)

  def test_required_unbound_raises(self):
    with pytest.raises(gin.GinError, match="needs_binding.value"):
      needs_binding()

  def test_required_bound(self):
    gin.parse_config("needs_binding.value = [1, 2]")
    assert needs_binding() == [1, 2]

  def test_unknown_param_raises(self):
    gin.parse_config("make_widget.nonexistent = 1")
    with pytest.raises(gin.GinError, match="nonexistent"):
      make_widget()

  def test_bind_and_query_parameter(self):
    gin.bind_parameter("make_widget.size", 7)
    assert gin.query_parameter("make_widget.size") == 7
    assert make_widget()["size"] == 7


class TestValues:

  def test_literals(self):
    for text, expected in [
        ("1", 1), ("1.5", 1.5), ("'abc'", "abc"), ("True", True),
        ("None", None), ("[1, 2]", [1, 2]), ("(1, 'a')", (1, "a")),
        ("{'k': 3}", {"k": 3}),
    ]:
      assert gin.parse_value(text) == expected

  def test_reference_injects_callable(self):
    gin.parse_config("""
      make_widget.size = 3
      make_gadget.widget = @make_widget
    """)
    out = make_gadget()
    assert callable(out["widget"])
    assert out["widget"]()["size"] == 3

  def test_evaluated_reference(self):
    gin.parse_config("""
      make_widget.size = 4
      make_gadget.widget = @make_widget()
    """)
    assert make_gadget()["widget"]["size"] == 4

  def test_reference_inside_list(self):
    gin.parse_config("make_gadget.widget = [@make_widget(), 7]")
    out = make_gadget()["widget"]
    assert out[1] == 7 and out[0]["size"] == 1

  def test_macro(self):
    gin.parse_config("""
      SIZE = 12
      make_widget.size = %SIZE
    """)
    assert make_widget()["size"] == 12

  def test_string_with_at_sign_not_a_ref(self):
    gin.parse_config("make_widget.color = 'user@host'")
    assert make_widget()["color"] == "user@host"

  def test_multiline_value(self):
    gin.parse_config("""
      make_widget.factory = [
          1,
          2,
          3,
      ]
    """)
    assert make_widget()["factory"] == [1, 2, 3]


class TestScopes:

  def test_scoped_binding(self):
    gin.parse_config("""
      make_widget.size = 1
      train/make_widget.size = 100
    """)
    assert make_widget()["size"] == 1
    with gin.config_scope("train"):
      assert make_widget()["size"] == 100

  def test_scoped_reference(self):
    gin.parse_config("""
      train/make_widget.size = 50
      make_gadget.widget = @train/make_widget()
    """)
    assert make_gadget()["widget"]["size"] == 50


class TestFilesAndDump:

  def test_parse_file_and_include(self, tmp_path):
    base = tmp_path / "base.gin"
    base.write_text("make_widget.size = 2\n")
    top = tmp_path / "top.gin"
    top.write_text(f"include '{base}'\nmake_widget.color = 'green'\n")
    gin.parse_config_files_and_bindings([str(top)],
                                        ["make_gadget.scale = 3.0"])
    assert make_widget() == {"size": 2, "color": "green", "factory": None}
    assert make_gadget()["scale"] == 3.0

  def test_config_str_roundtrip(self):
    gin.parse_config("""
      SIZE = 5
      make_widget.size = %SIZE
      train/make_widget.color = 'red'
    """)
    dumped = gin.config_str()
    gin.clear_config()
    gin.parse_config(dumped)
    assert make_widget()["size"] == 5

  def test_operative_config(self):
    gin.parse_config("make_widget.size = 8\nmake_widget.color = 'k'")
    make_widget()
    dump = gin.operative_config_str()
    assert "make_widget.size = 8" in dump


class TestReviewRegressions:
  """Pinned behaviors from code-review findings."""

  def test_unknown_configurable_binding_raises_at_parse(self):
    with pytest.raises(gin.GinError, match="No configurable matching"):
      gin.parse_config("fnn.x = 42")  # typo'd target

  def test_unknown_binding_skipped_with_skip_unknown(self):
    gin.parse_config("fnn.x = 42", skip_unknown=True)  # no raise

  def test_fully_qualified_binding_applies(self):
    gin.parse_config("tests.test_config.make_widget.size = 77")
    assert make_widget()["size"] == 77

  def test_compound_scope_beats_bare_scope(self):
    gin.parse_config("""
      a/b/make_widget.size = 1
      b/make_widget.size = 2
    """)
    with gin.config_scope("a"):
      with gin.config_scope("b"):
        assert make_widget()["size"] == 1  # most specific scope wins

  def test_external_configurable_does_not_mutate_original(self):
    class Plain:
      def __init__(self, x=1):
        self.x = x

    wrapped = gin.external_configurable(Plain, name="PlainThing")
    gin.bind_parameter("PlainThing.x", 9)
    assert Plain().x == 1       # original untouched
    assert wrapped().x == 9     # wrapper injects
    assert isinstance(wrapped(), Plain)

  def test_lazy_registration_in_process(self, tmp_path, monkeypatch):
    import sys

    (tmp_path / "lazy_reg_target_mod.py").write_text(
        "from tensor2robot_tpu import config as gin\n"
        "@gin.configurable\n"
        "def lazy_reg_fn(value=0):\n"
        "  return value\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    gin.register_lazy_configurables("lazy_reg_target_mod",
                                    ("lazy_reg_fn",))
    assert "lazy_reg_target_mod" not in sys.modules
    gin.parse_config("lazy_reg_fn.value = 5")  # triggers the import
    assert sys.modules["lazy_reg_target_mod"].lazy_reg_fn() == 5

  def test_lazy_package_registers_data_configurables(self):
    """run_t2r_trainer regression: `tensor2robot_tpu.data` resolves its
    exports lazily (PEP 562 — worker spawns must not pay the jax
    import), but a config binding one of its configurables must still
    parse right after the bare package import. Subprocess: the trainer
    registration path with clean module state."""
    import subprocess
    import sys

    code = (
        "import importlib, sys\n"
        "importlib.import_module('tensor2robot_tpu.data')\n"
        "assert 'jax' not in sys.modules, 'package import dragged jax'\n"
        "from tensor2robot_tpu import config as gin\n"
        "gin.parse_config('RandomInputGenerator.batch_size = 4')\n"
        "assert gin.query_parameter(\n"
        "    'RandomInputGenerator.batch_size') == 4\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True,
                   timeout=120)

  def test_broken_in_tree_family_raises(self, monkeypatch):
    """Every in-tree family's dependencies are installed: one that
    fails to import is a bug to surface, not a module to skip."""
    from absl import flags

    from tensor2robot_tpu.bin import run_t2r_trainer

    if not flags.FLAGS.is_parsed():
      flags.FLAGS.mark_as_parsed()
    monkeypatch.setattr(
        run_t2r_trainer, "_DEFAULT_MODULES",
        ("tensor2robot_tpu.models", "tensor2robot_tpu.no_such_family"))
    with pytest.raises(ImportError, match="no_such_family"):
      run_t2r_trainer._import_configurable_families()
