"""Portability-layer contract: every compat symbol resolves on the installed
JAX, behaves sanely, and no module outside ``repro/compat`` touches the
drifted JAX surface directly (AST lint — ``repro.analysis.lint``)."""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# (a) every public symbol resolves on the installed JAX
# ---------------------------------------------------------------------------
def test_all_public_symbols_resolve():
    for name in compat.__all__:
        obj = getattr(compat, name)
        assert obj is not None, f"compat.{name} resolved to None"


def test_version_detection():
    assert compat.JAX_VERSION == tuple(
        int(p) for p in jax.__version__.split(".")[:3])
    assert compat.JAX_VERSION >= (0, 9, 0)
    assert jax.__version__ in compat.version_summary()


def test_shard_map_runs_and_translates_check_kwarg():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    sm = compat.shard_map(lambda a: a * 2 + compat.axis_size("x") - 1,
                          mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                          check_vma=False)
    np.testing.assert_array_equal(np.asarray(sm(jnp.arange(4.))),
                                  np.arange(4.) * 2)
    with pytest.raises(TypeError):
        compat.shard_map(lambda a: a, mesh=mesh, in_specs=P("x"),
                         out_specs=P("x"), check_rep=False)


def test_axis_size_outside_mapping():
    assert compat.axis_size(None) == 1


def test_compiler_params_builds_and_drops_unknown():
    cp = compat.pallas_compiler_params(
        dimension_semantics=("parallel", "arbitrary"), collective_id=3)
    assert cp.dimension_semantics == ("parallel", "arbitrary")
    assert cp.collective_id == 3
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compat.pallas_compiler_params(totally_future_knob=1)
    assert any("totally_future_knob" in str(w.message) for w in caught)


def test_interpret_default_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert compat.interpret_default() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert compat.interpret_default() is False


def test_memory_space_helpers():
    ref = compat.VMEM((8, 128), jnp.float32)
    assert ref is not None
    assert compat.DMA_SEM is not None


def test_pallas_call_end_to_end():
    """A tiny kernel through compat.pallas_call with dict compiler params."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    out = compat.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(x.shape, lambda: (0, 0))],
        out_specs=pl.BlockSpec(x.shape, lambda: (0, 0)),
        compiler_params={"dimension_semantics": ()},
        interpret=True,
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2)


# ---------------------------------------------------------------------------
# (b) AST lint: drifted symbols only inside repro/compat
# ---------------------------------------------------------------------------
# The old grep-based scan lived here; it could not tell an import from a
# string mentioning one (the AST linter's own rule tables tripped it).
# repro.analysis.lint parses the files, so only REAL imports/attributes
# of the drifted surface count.
_COMPAT_RULES = ("compat-import", "bare-shard-map")


def _compat_violations(tops):
    from repro.analysis import lint
    return [v for v in lint.lint_tree(REPO, scope=tops)
            if v.rule in _COMPAT_RULES]


def test_no_drifted_symbols_outside_compat():
    hits = _compat_violations(("src",))
    assert not hits, ("drifted JAX symbols outside repro/compat "
                      "(import through repro.compat instead):\n"
                      + "\n".join(map(str, hits)))


def test_no_drifted_symbols_in_tests():
    hits = _compat_violations(("tests", "benchmarks", "examples"))
    assert not hits, ("drifted JAX symbols in tests "
                      "(import through repro.compat instead):\n"
                      + "\n".join(map(str, hits)))
