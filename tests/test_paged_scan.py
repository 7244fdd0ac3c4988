"""The paged pool rides the serving programs' layer scan in place.

``decode_step`` and ``prefill_chunk_step`` carry the stacked cache leaves
through ``lax.scan`` and read/write each layer at its index, so the compiled
programs hold no copy of a pool leaf (or of one layer of it) and no
broadcast that would build a stacked ``ys`` buffer.  The results must be
bit-identical to the per-layer form: slice each layer out, call
``pool_update_rows`` / ``pool_view`` without an index, restack.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh

from repro.configs.base import ParallelConfig, get_smoke_config
from repro.models import layers
from repro.models import model as M
from repro.models import serve as S
from repro.runtime.server import ServeConfig, Server


def _server(arch, keep_logits=False):
    cfg = get_smoke_config(arch)
    par = ParallelConfig(tp=1, dp=1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = M.init_model(jax.random.PRNGKey(0), cfg, par)
    sc = ServeConfig(max_batch=3, max_seq=64, eos_token=-1, max_new_tokens=4,
                     block_size=8, prefill_chunk=8, keep_logits=keep_logits)
    return Server(cfg, par, mesh, params, sc)


def _inputs(srv, program, rng):
    """Arguments after (params, caches): decode with one inactive slot and
    staggered positions; a chunk at offset 8 shorter than the chunk (its pad
    rows redirect to the null block)."""
    b, pages, c = srv.sc.max_batch, srv.pages, srv.sc.prefill_chunk
    v = srv.cfg.vocab_size
    i32 = np.int32
    if program == "decode":
        bt = np.zeros((b, pages), i32)
        bt[0, :3] = [3, 7, 1]
        bt[2, :2] = [5, 2]
        return (rng.integers(0, v, (b, 1)).astype(i32),
                np.array([17, 30, 9], i32), bt,
                np.array([True, False, True]))
    bt = np.zeros((1, pages), i32)
    bt[0, :3] = [4, 6, 8]
    return (rng.integers(0, v, (1, c)).astype(i32), bt, i32(1), i32(8),
            i32(5))


def _random_caches(srv, rng):
    """The server's cache tree, filled with values (not zeros) so every
    gathered and frozen row shows."""
    def fill(x):
        r = rng.standard_normal(x.shape).astype(np.float32)
        return jnp.asarray(r, x.dtype)
    return jax.tree.map(fill, srv.caches)


def _per_layer(body, x, periods_p, periods_c, n, carried):
    """Reference for ``serve._scan_periods``: the scan maps over the layers,
    so each layer's leaves are sliced out (``li`` is None: the pools are
    written and read without an index) and the new ones restacked."""
    x, new = lax.scan(lambda x, pc: body(x, *pc, None), x,
                      (tuple(periods_p), tuple(periods_c)))
    return x, list(new)


def _pool_shapes(srv):
    shapes = set()
    for leaf in jax.tree.leaves(srv.caches["periods"]):
        s = tuple(leaf.shape)
        shapes |= {s, (1,) + s[1:], s[1:]}
    return shapes


_OP = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} (copy|broadcast)\(")


@pytest.mark.parametrize("arch", ["minicpm_2b", "deepseek_v3_671b"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_programs_hold_no_pool_copies(arch, program):
    """The compiled programs copy no pool leaf (stacked or one layer) and
    broadcast none: the carried pool is scattered and gathered in place."""
    srv = _server(arch)
    fn = {"decode": srv._decode, "chunk": srv._chunk}[program]
    args = [jnp.asarray(a) for a in _inputs(srv, program,
                                            np.random.default_rng(0))]
    text = fn.lower(srv.params, srv.caches, *args).compile().as_text()
    shapes = _pool_shapes(srv)
    hits = [m.group(0) for m in _OP.finditer(text)
            if tuple(int(d) for d in m.group(1).split(",") if d) in shapes]
    assert hits == [], hits


@pytest.mark.parametrize("arch", ["minicpm_2b", "deepseek_v3_671b",
                                  "jamba_v01_52b", "rwkv6_3b"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_step_matches_per_layer_reference(arch, program, monkeypatch):
    """A paged decode step (with an inactive slot, whose dense state rows
    stay frozen) and a paged prefill chunk give the same tokens, logits and
    cache leaves, bit for bit, as the per-layer reference."""
    srv = _server(arch, keep_logits=True)
    rng = np.random.default_rng(1)
    caches = _random_caches(srv, rng)
    args = [jnp.asarray(a) for a in _inputs(srv, program, rng)]
    make = {"decode": Server._make_decode, "chunk": Server._make_chunk}[program]

    got = make(srv)(srv.params, jax.tree.map(jnp.copy, caches), *args)
    monkeypatch.setattr(S, "_scan_periods", _per_layer)
    want = make(srv)(srv.params, jax.tree.map(jnp.copy, caches), *args)

    (g_tok, g_caches, g_logits), (w_tok, w_caches, w_logits) = got, want
    np.testing.assert_array_equal(np.asarray(g_tok), np.asarray(w_tok))
    np.testing.assert_array_equal(np.asarray(g_logits), np.asarray(w_logits))
    g_leaves, w_leaves = jax.tree.leaves(g_caches), jax.tree.leaves(w_caches)
    assert len(g_leaves) == len(w_leaves) > 0
    before = jax.tree.leaves(caches)
    changed = 0
    for g, w, b in zip(g_leaves, w_leaves, before):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        changed += not np.array_equal(np.asarray(g), np.asarray(b))
    assert changed > 0          # the step wrote something


def test_layer_index_matches_sliced_pool():
    """``pool_update_rows`` / ``pool_view`` at a layer index of a stacked
    pool equal the same calls on that layer's slice, restacked; the other
    layers are untouched.  Pad rows past ``valid`` land in the null block."""
    rng = np.random.default_rng(2)
    stack = jnp.asarray(rng.standard_normal((3, 6, 4, 2, 8)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((2, 5, 2, 8)), jnp.bfloat16)
    bt = jnp.asarray([[2, 4, 0], [5, 1, 3]], jnp.int32)
    start = jnp.asarray([1, 6], jnp.int32)
    valid = jnp.asarray([5, 3], jnp.int32)
    li = jnp.int32(1)

    got = layers.pool_update_rows(stack, new, bt, start, valid, layer=li)
    one = layers.pool_update_rows(stack[1], new, bt, start, valid)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(stack.at[1].set(one)))
    np.testing.assert_array_equal(np.asarray(layers.pool_view(got, bt, li)),
                                  np.asarray(layers.pool_view(one, bt)))
    # row 1's pad rows (i >= 3) went to block 0, not to its table's blocks
    assert not np.array_equal(np.asarray(one[0]), np.asarray(stack[1, 0]))
    np.testing.assert_array_equal(np.asarray(one[3, 1:]),
                                  np.asarray(stack[1, 3, 1:]))
