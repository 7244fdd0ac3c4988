"""Seeded-violation fixtures for repro.analysis.{seamcheck,lint,check}.

Every contract the checker enforces is exercised from BOTH sides: a clean
construct must pass, and a deliberately seeded violation of each rule must
be reported (with an actionable message).  All tracing is abstract
(``make_jaxpr`` + ``axis_env``) — no devices, no subprocesses.
"""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.analysis import lint, seamcheck

TP = 4
ENV = [("model", TP)]


def _colls(fn, *args):
    return seamcheck.collect_collectives(
        jax.make_jaxpr(fn, axis_env=ENV)(*args))


# ---------------------------------------------------------------------------
# walker
# ---------------------------------------------------------------------------
def test_walker_psum_scatter_traces_as_reduce_scatter():
    x = jax.ShapeDtypeStruct((TP, 8), jnp.float32)
    cs = _colls(  # lint: allow(raw-collective)
        lambda a: lax.psum_scatter(a, "model"), x)
    assert [c.prim for c in cs] == ["reduce_scatter"]


def test_walker_counts_all_to_all():
    x = jax.ShapeDtypeStruct((TP, 8), jnp.float32)
    cs = _colls(  # lint: allow(raw-collective)
        lambda a: lax.all_to_all(a, "model", 0, 0, tiled=True), x)
    assert [c.prim for c in cs] == ["all_to_all"]
    assert "all_to_all" in seamcheck.CENSUS_PRIMS


def test_census_reports_stray_all_to_all():
    x = jax.ShapeDtypeStruct((TP, 16, 64), jnp.float32)
    cs = _colls(  # lint: allow(raw-collective)
        lambda a: lax.all_to_all(a, "model", 0, 0, tiled=True), x)
    errs = seamcheck.census_errors(cs, "model", min_elems=TP * 16 * 64)
    assert len(errs) == 1
    assert "unattributed" in errs[0] and "all_to_all" in errs[0]


def test_walker_counts_scan_trips_weighted():
    x = jax.ShapeDtypeStruct((8,), jnp.float32)

    def f(a):
        def body(c, _):
            return lax.psum(c, "model"), ()
        out, _ = lax.scan(body, a, None, length=5)
        return out

    jx = jax.make_jaxpr(f, axis_env=ENV)(x)
    assert seamcheck.count(jx, "psum") == 1
    assert seamcheck.count(jx, "psum", weighted=True) == 5


def test_walker_scope_survives_transpose():
    x = jax.ShapeDtypeStruct((8,), jnp.float32)

    def f(a):
        with jax.named_scope("seam_fixture"):
            return jnp.sum(lax.psum(a, "model") ** 2)

    cs = _colls(lambda a: jax.grad(f)(a), x)
    assert cs and all(c.seam_tagged for c in cs)


# ---------------------------------------------------------------------------
# contract 1: census (stray full-activation collective)
# ---------------------------------------------------------------------------
def test_census_reports_stray_full_activation_all_gather():
    x = jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)
    cs = _colls(  # lint: allow(raw-collective)
        lambda a: lax.all_gather(a, "model", axis=1, tiled=True), x)
    errs = seamcheck.census_errors(cs, "model", min_elems=2 * 16 * 64)
    assert len(errs) == 1
    assert "unattributed" in errs[0] and "all_gather" in errs[0]
    assert "(2, 16, 64)" in errs[0]          # shapes in the report


def test_census_passes_seam_tagged_and_tiny_collectives():
    x = jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.float32)

    def f(a, b):
        with jax.named_scope("seam_fixture"):
            # lint: allow(raw-collective)
            big = lax.all_gather(a, "model", axis=1, tiled=True)
        tiny = lax.psum(b, "model")          # xent-scale: under threshold
        return big, tiny

    errs = seamcheck.census_errors(_colls(f, x, t), "model",
                                   min_elems=2 * 16 * 64)
    assert errs == []


def test_census_ignores_other_axes():
    x = jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)
    cs = seamcheck.collect_collectives(jax.make_jaxpr(
        lambda a: lax.psum(a, "data"),
        axis_env=[("data", 2), ("model", TP)])(x))
    assert seamcheck.census_errors(cs, "model", min_elems=1) == []


# ---------------------------------------------------------------------------
# contract 2: cotangent completion (the PR 5 mamba x_proj bug class)
# ---------------------------------------------------------------------------
def _rank_exclusive_consumer(complete: bool):
    """y = replicated(x) @ w_shard: w is rank-exclusive, so dy arrives as a
    per-rank partial and dx must be psum'd — the buggy variant skips it."""
    @jax.custom_vjp
    def f(x, w):
        return x @ w

    def fwd(x, w):
        return x @ w, (x, w)

    def bwd(res, dy):
        x, w = res
        if complete:
            # the repo convention (_fused_bwd): complete the per-rank
            # partial FIRST, then contract against rank-exclusive operands
            dy = lax.psum(dy, "model")
        return dy @ w.T, x.T @ dy

    f.defvjp(fwd, bwd)
    return f


@pytest.mark.parametrize("complete", [True, False])
def test_cotangent_completion_catches_missing_psum(complete):
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    f = _rank_exclusive_consumer(complete)
    ct = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    errs = seamcheck.check_cotangent_completion(
        f, (x, w), ct, axis_env=ENV, expect_complete=True,
        label="fixture")
    if complete:
        assert errs == []
    else:
        assert errs and "raw (uncompleted) cotangent contraction" in errs[0]


def test_cotangent_spurious_completion_reported():
    # rank-exclusive output: the cotangent arrives FULL; a psum on its
    # path double-counts and must be flagged
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    f = _rank_exclusive_consumer(True)
    ct = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    errs = seamcheck.check_cotangent_completion(
        f, (x, w), ct, axis_env=ENV, expect_complete=False,
        label="fixture")
    assert errs and "spurious cotangent completion" in errs[0]


def test_fusedop_cotangent_matrix_clean():
    assert seamcheck.fusedop_cotangent_errors(tp=TP) == []


# ---------------------------------------------------------------------------
# contract 3 + end-to-end: one config, both layouts, in-process
# ---------------------------------------------------------------------------
def test_layout_errors_flag_misplaced_collectives():
    x = jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)
    ag = _colls(  # lint: allow(raw-collective)
        lambda a: lax.all_gather(a, "model", axis=1, tiled=True), x)
    errs = seamcheck.layout_errors(ag, None, "seq", "decomposed")
    assert errs and "standalone all_gather" in errs[0]

    pp = _colls(lambda a: lax.ppermute(  # lint: allow(raw-collective)
        a, "model", [(i, (i + 1) % TP) for i in range(TP)]), x)
    errs = seamcheck.layout_errors(pp, None, "hidden", "decomposed")
    assert errs and "ppermute" in errs[0]
    # decode must stay replicated
    errs = seamcheck.layout_errors([], pp, "hidden", "decomposed")
    assert errs and "decode" in errs[0]


def test_one_config_seam_contracts_clean():
    for layout in ("seq", "hidden"):
        assert seamcheck.check_config("minicpm_2b", layout) == []


def test_chunked_prefill_census_lane():
    # the serving admission path (prefill_chunk_step: [1, C] tokens +
    # traced slot/off/chunk_len scalars over the paged pools) is census'd
    # like decode: replicated layout, every full-chunk collective
    # seam-tagged, no ppermute ring / sequence reduce_scatter
    from repro.configs.base import ParallelConfig, get_smoke_config
    from repro.tuning.plans import PlanSet

    cfg = get_smoke_config("minicpm_2b")
    par = ParallelConfig(tp=TP, dp=1, overlap_mode="decomposed",
                         scatter_axis="hidden")
    plans = PlanSet.uniform("decomposed").with_scatter_axis("hidden")
    chunk = 16
    jx = seamcheck.trace_prefill_chunk(cfg, par, plans, tp=TP, b=2,
                                       s_max=64, chunk=chunk)
    cs = seamcheck.collect_collectives(jx)
    assert cs, "chunked prefill admission must trace collectives"
    big = [c for c in cs if c.elems >= chunk * cfg.d_model]
    assert big, "no full-chunk collective traced (threshold too high?)"
    assert all(c.seam_tagged for c in big), \
        [c.describe() for c in big if not c.seam_tagged]
    assert seamcheck.census_errors(cs, "model", chunk * cfg.d_model) == []
    assert seamcheck.layout_errors([], cs, "hidden", "decomposed") == []


# ---------------------------------------------------------------------------
# lint fixtures
# ---------------------------------------------------------------------------
def _lint(src, path="src/repro/models/fixture.py"):
    return lint.lint_source(src, path)


def test_lint_compat_import_rule():
    for src in ("from jax.experimental.shard_map import shard_map\n",
                "from jax.core import Literal\n", "from jax import core\n",
                "import jax.core\n", "ok = isinstance(v, jax.core.Var)\n"):
        assert [v.rule for v in _lint(src)] == ["compat-import"], src
    # exempt inside compat/
    for src in ("from jax.experimental.shard_map import shard_map\n",
                "from jax.core import Literal\n"):
        assert _lint(src, "src/repro/compat/shims.py") == [], src


def test_lint_bare_shard_map_rule():
    assert [v.rule for v in _lint("from jax import shard_map\n")] == \
        ["bare-shard-map"]
    assert [v.rule for v in _lint("f = jax.shard_map(g)\n")] == \
        ["bare-shard-map"]


def test_lint_private_backend_rule():
    vs = _lint("y = overlap._rs_ring(x, w, 'model')\n")
    assert [v.rule for v in vs] == ["private-backend"]
    vs = _lint("from repro.core.overlap import _fused_bwd\n")
    assert [v.rule for v in vs] == ["private-backend"]
    assert _lint("op = overlap.FusedOp(kind='ag', axis='model')\n") == []


def test_lint_removed_wrapper_rule():
    vs = _lint("y = ag_matmul(x, w, 'model')\n")
    assert [v.rule for v in vs] == ["removed-wrapper"]
    # the *_ref oracles and string literals no longer trip it (grep did)
    assert _lint("y = ag_matmul_ref(x, w, 'model')\n") == []
    assert _lint("code = 'ag_matmul(x, w)'\n") == []


def test_lint_raw_collective_rule_and_escape():
    src = "y = lax.ppermute(x, 'model', perm)\n"
    assert [v.rule for v in _lint(src)] == ["raw-collective"]
    # the MoE-exchange blind spot: all_to_all and psum_scatter are seam
    # transports too (PR 7) — a raw call outside the seam layer must trip
    assert [v.rule for v in _lint("y = lax.all_to_all(x, 'model', 0, 0)\n")] \
        == ["raw-collective"]
    assert [v.rule for v in _lint("y = lax.psum_scatter(x, 'data')\n")] == \
        ["raw-collective"]
    # allowed files
    assert _lint(src, "src/repro/core/overlap.py") == []
    assert _lint(src, "src/repro/parallel/sharding.py") == []
    # per-line escape, on the line or the line above
    assert _lint("y = lax.ppermute(x, 'model', p)"
                 "  # lint: allow(raw-collective)\n") == []
    assert _lint("# lint: allow(raw-collective)\n"
                 "y = lax.ppermute(x, 'model', p)\n") == []
    # escape for one rule does not silence another — and since the
    # raw-collective escape suppresses nothing here, it is itself stale
    assert [v.rule for v in _lint(
        "y = ag_matmul(x)  # lint: allow(raw-collective)\n")] == \
        ["removed-wrapper", "stale-allow"]


def test_lint_stale_allow_rule():
    # an escape that suppresses nothing is a violation at its comment line
    vs = _lint("x = 1  # lint: allow(raw-collective)\n")
    assert [v.rule for v in vs] == ["stale-allow"]
    assert vs[0].line == 1 and "suppresses no raw-collective" in vs[0].message
    # unknown rule names can never suppress anything
    vs = _lint("x = 1  # lint: allow(not-a-rule)\n")
    assert [v.rule for v in vs] == ["stale-allow"]
    assert "unknown rule" in vs[0].message
    # a USED escape is not stale (coverage window: its line and the next)
    assert _lint("# lint: allow(raw-collective)\n"
                 "y = lax.ppermute(x, 'model', p)\n") == []
    # escape-shaped text inside a string literal is NOT an escape: it
    # neither suppresses a finding nor counts as stale (tokenize comments)
    assert _lint("s = '# lint: allow(raw-collective)'\n") == []
    vs = _lint("s = 'x  # lint: allow(raw-collective)'\n"
               "y = lax.ppermute(x, 'model', p)\n")
    assert [v.rule for v in vs] == ["raw-collective"]
    # the stale-allow finding itself honors the escape mechanism
    assert _lint(
        "x = 1  # lint: allow(raw-collective, stale-allow)\n") == []


def test_lint_clean_tree():
    assert lint.lint_tree() == []


def test_check_cli_lint_lane():
    from repro.analysis import check
    assert check.main(["--lint", "-q"]) == 0
