"""Serving paths: prefill (cache-building forward) and single-token decode.

Cache layout is GLOBAL (``compat.shard_map`` slices it): per layer-position
trees whose shapes come from ``cache_specs``.  Decode is the paper's
vLLM-style TP pattern: replicated activations, local-head attention over the
sharded KV cache, row-parallel output GEMM + AllReduce (the FLUX decode
seam).

Continuous-batching contract (what the runtime Server relies on):

* ``decode_step`` takes ``pos: [B]`` — a PER-SLOT position vector.  Every
  batch row RoPE-rotates at, masks to, and cache-writes at its OWN
  position (per-row ``dynamic_update_slice``), so slots at staggered
  sequence positions decode together in one fixed-shape dispatch without
  touching each other's cache rows.  A scalar ``pos`` still broadcasts (all
  rows in lockstep — the bench/smoke path).  The optional ``active: [B]``
  bool mask freezes the dense recurrent-state rows (Mamba conv/SSM, RWKV
  wkv/shift) of non-generating slots — the server passes its ready mask so
  a slot mid-chunked-prefill survives the interleaved full-batch decodes.
* ``prefill_step`` takes optional ``lengths: [B]`` — per-row true prompt
  lengths of a RIGHT-PADDED token batch.  Attention families are pad-safe
  by causality; the state families (Mamba SSM/conv, RWKV WKV/token-shift)
  freeze their recurrent state at each row's true length (identity decay +
  zero input on pad positions), and the next-token logits are read at
  ``lengths - 1`` per row.  The returned caches are therefore exactly what
  a token-by-token decode of the unpadded prompt would have produced —
  admission scatters them into a slot's rows in one dispatch.

How caches flow through the scanned layer loop (``_scan_periods``): the
PAGED programs (``block_tables`` given, and every ``prefill_chunk_step``)
carry the stacked ``caches["periods"]`` leaves in the ``lax.scan`` carry
beside ``x`` and scan over (period params, layer index).  Each period
scatters its new K/V rows straight into ``pool[layer, block, row]`` and
gathers its view from ``pool[layer, bt]`` (``layers.pool_update_rows`` /
``pool_view`` with ``layer``); the dense per-slot state leaves (Mamba
conv/SSM, RWKV wkv/shift) are read at, and written back with a
dynamic-update-slice at, the same index.  No layer is sliced out and no
stacked output is rebuilt, so the donated pool is updated in place.  The
DENSE caches (``block_tables=None``) map over the layers as scan xs/ys.
The unscanned ``lead`` layers hold unstacked leaves and take no index.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, DENSE_FFN, MLA, MAMBA, MOE_FFN, RWKV,
                                ModelConfig, ParallelConfig)
from repro.models import attention, ffn, layers, mamba, rwkv
from repro.models.model import (_maybe_gather_zero3, expanded_pattern,
                                n_periods, zero3_flags)
from repro.parallel.sharding import (TPContext, ceil_mult, gather_ranks,
                                     pad_kv_heads, pad_heads, pad_vocab)

Array = jax.Array


# ---------------------------------------------------------------------------
# Cache specs (global shapes + PartitionSpecs)
# ---------------------------------------------------------------------------
def _mixer_cache_spec(kind: str, cfg: ModelConfig, par: ParallelConfig,
                      batch: int, s_max: int, dp_axes: Tuple[str, ...],
                      pool: Optional[Tuple[int, int]] = None):
    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    tp = par.tp
    if kind == ATTN:
        hkv = pad_kv_heads(cfg.num_kv_heads, tp)
        dh = cfg.resolved_head_dim
        if pool is not None:
            nb, bs = pool
            sds = {"k": jax.ShapeDtypeStruct((nb, bs, hkv, dh), jnp.bfloat16),
                   "v": jax.ShapeDtypeStruct((nb, bs, hkv, dh), jnp.bfloat16)}
            spec = {"k": P(None, None, "model", None),
                    "v": P(None, None, "model", None)}
            return sds, spec
        sds = {"k": jax.ShapeDtypeStruct((batch, s_max, hkv, dh), jnp.bfloat16),
               "v": jax.ShapeDtypeStruct((batch, s_max, hkv, dh), jnp.bfloat16)}
        spec = {"k": P(dp, None, "model", None), "v": P(dp, None, "model", None)}
        return sds, spec
    if kind == MLA:
        # one row per token: the latent, the shared rope key and zero
        # lanes (``attention.latent_width``)
        shape = ((*pool,) if pool is not None else (batch, s_max)) + (
            attention.latent_width(cfg),)
        return ({"latent": jax.ShapeDtypeStruct(shape, jnp.bfloat16)},
                {"latent": P(None if pool is not None else dp, None, None)})
    if kind == MAMBA:
        d_in, _, d_state, d_conv = mamba._dims(cfg, tp)
        sds = {"conv": jax.ShapeDtypeStruct((batch, d_conv - 1, d_in),
                                            jnp.bfloat16),
               "ssm": jax.ShapeDtypeStruct((batch, d_in, d_state),
                                           jnp.float32)}
        spec = {"conv": P(dp, None, "model"), "ssm": P(dp, "model", None)}
        return sds, spec
    if kind == RWKV:
        n_heads, dh, _ = rwkv._dims(cfg, tp)
        sds = {"state": jax.ShapeDtypeStruct((batch, n_heads, dh, dh),
                                             jnp.float32),
               "last": jax.ShapeDtypeStruct((batch, cfg.d_model), jnp.bfloat16)}
        spec = {"state": P(dp, "model", None, None), "last": P(dp, None)}
        return sds, spec
    raise ValueError(kind)


def _ffn_cache_spec(kind: str, cfg: ModelConfig, par: ParallelConfig,
                    batch: int, dp_axes: Tuple[str, ...]):
    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    if kind == RWKV:
        return ({"last": jax.ShapeDtypeStruct((batch, cfg.d_model),
                                              jnp.bfloat16)},
                {"last": P(dp, None)})
    return {}, {}


def cache_specs(cfg: ModelConfig, par: ParallelConfig, batch: int, s_max: int,
                dp_axes: Tuple[str, ...] = ("data",),
                pool: Optional[Tuple[int, int]] = None):
    """Returns (ShapeDtypeStruct tree, PartitionSpec tree) for the full-model
    cache: {"lead": [...], "periods": [stacked per pattern position]}.

    With ``pool=(num_blocks, block_size)`` the attention-family leaves
    (GQA K/V, MLA latent) become shared ``[num_blocks, block_size, ...]``
    physical pools addressed through per-slot block tables (block ids are
    layer-agnostic: one allocation indexes every layer's pool leaf).  The
    state families (Mamba conv/SSM, RWKV wkv/shift) have no sequence dim
    to page — they stay dense per-slot ``[batch, ...]``."""
    pat = expanded_pattern(cfg)
    lead = cfg.leading_dense_layers
    reps = n_periods(cfg)

    def one(kind_pair):
        msds, mspec = _mixer_cache_spec(kind_pair[0], cfg, par, batch, s_max,
                                        dp_axes, pool)
        fsds, fspec = _ffn_cache_spec(kind_pair[1], cfg, par, batch, dp_axes)
        return ({"mixer": msds, "ffn": fsds},
                {"mixer": mspec, "ffn": fspec})

    sds: Dict[str, Any] = {"lead": [], "periods": []}
    spec: Dict[str, Any] = {"lead": [], "periods": []}
    for i in range(lead):
        s_, p_ = one(pat[i])
        sds["lead"].append(s_)
        spec["lead"].append(p_)
    for kp in cfg.pattern:
        s_, p_ = one(kp)
        s_ = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((reps, *x.shape), x.dtype), s_)
        p_ = jax.tree.map(lambda sp: P(*([None] + list(sp))), p_,
                          is_leaf=lambda x: isinstance(x, P))
        sds["periods"].append(s_)
        spec["periods"].append(p_)
    return sds, spec


def paged_cache_specs(cfg: ModelConfig, par: ParallelConfig, num_blocks: int,
                      block_size: int, max_batch: int):
    """Cache specs for the paged serving runtime (see ``cache_specs``).
    Paged serving is per-replica — continuous batching fills slots from a
    local queue, so no leaf carries a dp axis."""
    return cache_specs(cfg, par, max_batch, 0, dp_axes=(),
                       pool=(num_blocks, block_size))


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def _mixer_decode(kind: str, p: Dict, x: Array, cache: Dict, pos, ctx,
                  cfg: ModelConfig, bt=None, li=None):
    if kind == ATTN:
        if bt is not None:
            return attention.gqa_decode_paged(p, x, cache, bt, pos, ctx, cfg,
                                              li)
        return attention.gqa_decode(p, x, cache, pos, ctx, cfg)
    if kind == MLA:
        if bt is not None:
            return attention.mla_decode_paged(p, x, cache, bt, pos, ctx, cfg,
                                              li)
        return attention.mla_decode(p, x, cache, pos, ctx, cfg)
    if kind == MAMBA:
        return mamba.mamba_decode(p, x, cache, pos, ctx, cfg)
    if kind == RWKV:
        return rwkv.rwkv_time_decode(p, x, cache, ctx, cfg)
    raise ValueError(kind)


def _ffn_decode(kind: str, p: Dict, x: Array, cache: Dict, ctx,
                cfg: ModelConfig):
    if kind == DENSE_FFN:
        return ffn.ffn_decode(p, x, ctx, cfg.norm_eps), cache
    if kind == MOE_FFN:
        return ffn.moe_serve(p, x, ctx, cfg), cache
    if kind == RWKV:
        return rwkv.rwkv_channel_decode(p, x, cache, ctx, cfg)
    raise ValueError(kind)


def _at_layer(cache: Dict, li) -> Dict:
    """Layer ``li`` of a carried layer-stacked cache tree; the tree itself
    when ``li`` is None (an unstacked lead layer, or a dense scan's xs)."""
    if li is None:
        return cache
    return jax.tree.map(
        lambda v: lax.dynamic_index_in_dim(v, li, keepdims=False), cache)


def _put_layer(cache: Dict, new: Dict, li) -> Dict:
    """Write one layer's tree back into the carried stack at ``li``
    (``new`` itself when ``li`` is None)."""
    if li is None:
        return new
    return jax.tree.map(
        lambda v, n: lax.dynamic_update_index_in_dim(v, n.astype(v.dtype),
                                                     li, 0), cache, new)


def _scan_periods(body, x: Array, periods_p, periods_c, n: int,
                  carried: bool):
    """Run ``body(x, p, c, li) -> (x, new_c)`` over the ``n`` stacked
    periods.  ``carried``: the caches ride the scan CARRY whole and each
    period reads and writes its layer of the stacked leaves at the traced
    index ``li`` — the compiled loop then updates the paged pools in place
    instead of slicing each layer out and restacking every leaf.  Otherwise
    they map over the layers as xs/ys and ``body`` gets ``li=None``."""
    periods_p, periods_c = tuple(periods_p), tuple(periods_c)
    if not carried:
        x, new = lax.scan(lambda x, pc: body(x, *pc, None), x,
                          (periods_p, periods_c))
        return x, list(new)

    def step(carry, xs):
        return body(carry[0], xs[0], carry[1], xs[1]), None

    (x, new), _ = lax.scan(step, (x, periods_c),
                           (periods_p, jnp.arange(n, dtype=jnp.int32)))
    return x, list(new)


def _freeze_inactive(new: Dict, old: Dict, active) -> Dict:
    """Mask a dense per-slot cache write-back to the ACTIVE rows only.  The
    state families (Mamba conv/SSM, RWKV wkv/token-shift) rewrite every
    batch row unconditionally, so a slot that is mid-prefill (its chunked
    prefill threads state across dispatches) or empty must get its rows
    restored — the dense analogue of the paged attention caches'
    null-block redirect."""
    return jax.tree.map(
        lambda n, o: jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o.astype(n.dtype)),
        new, old)


def _block_decode(kind_pair, lp: Dict, lc: Dict, x: Array, pos, ctx, cfg,
                  par: ParallelConfig, z3=None, layer=None, bt=None,
                  active=None, li=None):
    """``li``: this block's index into the carried layer-stacked caches
    ``lc`` (paged scan; None for unstacked leaves)."""
    lp = _maybe_gather_zero3(lp, par, z3)
    ctx = ctx.with_layer(layer)
    if bt is not None and kind_pair[0] in (ATTN, MLA):
        # paged attention pools ([num_blocks, ...]) are written in place
        # and already protected by the null-block redirect
        dy, mc = _mixer_decode(kind_pair[0], lp["mixer"], x, lc["mixer"],
                               pos, ctx, cfg, bt=bt, li=li)
    else:
        old = _at_layer(lc["mixer"], li)
        dy, mc = _mixer_decode(kind_pair[0], lp["mixer"], x, old, pos, ctx,
                               cfg, bt=bt)
        if active is not None:
            # every dense [B, ...] cache needs the row mask
            mc = _freeze_inactive(mc, old, active)
        mc = _put_layer(lc["mixer"], mc, li)
    x = x + dy
    old = _at_layer(lc["ffn"], li)
    dy, fc = _ffn_decode(kind_pair[1], lp["ffn"], x, old, ctx, cfg)
    if active is not None and kind_pair[1] == RWKV:
        fc = _freeze_inactive(fc, old, active)
    return x + dy, {"mixer": mc, "ffn": _put_layer(lc["ffn"], fc, li)}


def decode_step(params: Dict, caches: Dict, tokens: Array, pos,
                ctx: TPContext, cfg: ModelConfig, par: ParallelConfig,
                block_tables=None, active=None, with_logits: bool = False):
    """One greedy decode step.  tokens: [B_loc, 1] int32; pos: [B_loc] int32
    per-slot write positions (a scalar broadcasts to all rows).  With
    ``block_tables`` ([B_loc, pages] int32) the attention caches are paged
    pools and each row reads/writes through its own table (all-zero rows
    redirect to the null block — inactive slots are harmless).

    ``active`` ([B_loc] bool, optional): rows that are actually GENERATING.
    Inactive rows keep their dense per-slot state caches (Mamba conv/SSM,
    RWKV wkv/token-shift ``last``) bit-untouched — without the mask a
    full-batch decode would advance a mid-prefill slot's chunk-threaded
    recurrent state with garbage pad-token input.  Attention pool leaves
    need no masking (null-block redirect); omitting ``active`` keeps the
    legacy all-rows-advance behavior.  Returns (next_token [B_loc,1], new
    caches), and with ``with_logits`` also the fp32 next-token logits of
    this rank's vocab shard ([B_loc, V_pad/TP])."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                           (tokens.shape[0],))
    if active is not None:
        active = jnp.asarray(active, bool).reshape(-1)
    # decode ALWAYS runs the replicated activation layout: a one-token
    # "sequence" cannot shard, and the decode seams are kind="ar"
    ctx = ctx.with_layout(False)
    v_pad = pad_vocab(cfg.vocab_size, par.tp)
    x = layers.embed_lookup(params["embed"], tokens, ctx, v_pad)
    x = x.astype(cfg.compute_dtype)

    pat = expanded_pattern(cfg)
    z3 = zero3_flags(cfg, par)
    new_caches: Dict[str, Any] = {"lead": [], "periods": None}
    lead = cfg.leading_dense_layers
    for i in range(lead):
        x, nc = _block_decode(pat[i], params["lead"][i], caches["lead"][i],
                              x, pos, ctx, cfg, par,
                              z3["lead"][i] if z3["lead"] else None, layer=i,
                              bt=block_tables, active=active)
        new_caches["lead"].append(nc)

    def period_body(x, stacked_p, stacked_c, li):
        ncs = []
        for p_i, kp in enumerate(cfg.pattern):
            x, nc = _block_decode(kp, stacked_p[p_i], stacked_c[p_i], x, pos,
                                  ctx, cfg, par,
                                  z3["periods"][p_i] if z3["periods"] else None,
                                  layer=lead + p_i, bt=block_tables,
                                  active=active, li=li)
            ncs.append(nc)
        return x, tuple(ncs)

    x, new_caches["periods"] = _scan_periods(
        period_body, x, params["periods"], caches["periods"], n_periods(cfg),
        carried=block_tables is not None)

    h = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.head_logits(h, params)              # [B,1,V/TP] local
    nxt = vocab_parallel_argmax(logits[:, -1], ctx, v_pad, cfg.vocab_size)
    return _step_outputs(nxt, new_caches, logits, with_logits)


def _step_outputs(nxt, caches, logits, with_logits: bool):
    out = (nxt[:, None], caches)
    return out + (logits[:, -1].astype(jnp.float32),) if with_logits else out


def vocab_parallel_argmax(logits_loc: Array, ctx: TPContext,
                          v_pad: int, vocab_real: Optional[int] = None
                          ) -> Array:
    """Greedy sampling over vocab-sharded logits [B, V/TP] -> [B] int32."""
    v_loc = logits_loc.shape[-1]
    if vocab_real is not None and vocab_real < v_pad:
        col = ctx.tp_index() * v_loc + jnp.arange(v_loc)
        logits_loc = jnp.where(col < vocab_real, logits_loc, -jnp.inf)
    loc_idx = jnp.argmax(logits_loc, axis=-1)
    loc_val = jnp.take_along_axis(logits_loc, loc_idx[:, None], axis=-1)[:, 0]
    if ctx.axis is None or ctx.tp == 1:
        return loc_idx.astype(jnp.int32)
    glob_idx = loc_idx + ctx.tp_index() * v_loc
    vals = gather_ranks(loc_val, ctx.axis)                # [B, TP]
    idxs = gather_ranks(glob_idx, ctx.axis)               # [B, TP]
    best = jnp.argmax(vals, axis=-1)
    return jnp.take_along_axis(idxs, best[:, None], axis=-1)[:, 0].astype(
        jnp.int32)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------
def _mixer_prefill(kind: str, p, x, ctx, cfg, lengths=None):
    if kind == ATTN:
        # causal mask keeps rows < length independent of right-padding
        return attention.gqa_train(p, x, ctx, cfg, with_cache=True)
    if kind == MLA:
        return attention.mla_train(p, x, ctx, cfg, with_cache=True)
    if kind == MAMBA:
        return mamba.mamba_train(p, x, ctx, cfg, with_cache=True,
                                 lengths=lengths)
    if kind == RWKV:
        return rwkv.rwkv_time_train(p, x, ctx, cfg, with_cache=True,
                                    lengths=lengths)
    raise ValueError(kind)


def _ffn_prefill(kind: str, p, x, ctx, cfg, lengths=None):
    if kind == DENSE_FFN:
        return ffn.ffn_train(p, x, ctx, cfg.norm_eps), {}
    if kind == MOE_FFN:
        y, _ = ffn.moe_train(p, x, ctx, cfg, cfg.norm_eps, lengths=lengths)
        return y, {}
    if kind == RWKV:
        return rwkv.rwkv_channel_train(p, x, ctx, cfg, with_cache=True,
                                       lengths=lengths)
    raise ValueError(kind)


def _block_prefill(kind_pair, lp, x, ctx, cfg, par, z3=None, layer=None,
                   lengths=None):
    lp = _maybe_gather_zero3(lp, par, z3)
    ctx = ctx.with_layer(layer)
    dy, mc = _mixer_prefill(kind_pair[0], lp["mixer"], x, ctx, cfg, lengths)
    x = x + dy
    dy, fc = _ffn_prefill(kind_pair[1], lp["ffn"], x, ctx, cfg, lengths)
    return x + dy, {"mixer": mc, "ffn": fc}


def prefill_step(params: Dict, batch: Dict, ctx: TPContext, cfg: ModelConfig,
                 par: ParallelConfig, lengths=None):
    """Full-sequence prefill: returns (next_token [B_loc,1], caches).

    Prefill runs the plan-resolved activation layout (sequence-sharded by
    default — the SP memory win applies to the longest activations in
    serving); decode (``decode_step``) always forces the replicated layout.

    ``lengths`` ([B_loc] int32, optional): per-row true prompt lengths of a
    right-padded batch — caches freeze at each row's length (state
    families) and logits are read at ``lengths - 1`` per row (see module
    docstring)."""
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
    v_pad = pad_vocab(cfg.vocab_size, par.tp)
    if "embeds" in batch:
        x = batch["embeds"]
    else:
        x = layers.embed_lookup(params["embed"], batch["tokens"], ctx, v_pad)
    x = x.astype(cfg.compute_dtype)

    pat = expanded_pattern(cfg)
    z3 = zero3_flags(cfg, par)
    caches: Dict[str, Any] = {"lead": [], "periods": None}
    lead = cfg.leading_dense_layers
    for i in range(lead):
        x, nc = _block_prefill(pat[i], params["lead"][i], x, ctx, cfg, par,
                               z3["lead"][i] if z3["lead"] else None, layer=i,
                               lengths=lengths)
        caches["lead"].append(nc)

    def period_body(x, stacked_p):
        ncs = []
        for p_i, kp in enumerate(cfg.pattern):
            x, nc = _block_prefill(kp, stacked_p[p_i], x, ctx, cfg, par,
                                   z3["periods"][p_i] if z3["periods"] else None,
                                   layer=lead + p_i, lengths=lengths)
            ncs.append(nc)
        return x, tuple(ncs)

    x, stacked_caches = lax.scan(period_body, x, tuple(params["periods"]))
    caches["periods"] = list(stacked_caches)

    h = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    # only each row's LAST true position's logits feed the next token
    # (gather_seq: no-op in the replicated layout, ring transport under SP)
    if lengths is None:
        h_last = ctx.gather_seq(h[:, -1:], "head_ag")[:, -1:]
    else:
        h_last = layers.take_rows(ctx.gather_seq(h, "head_ag"),
                                  lengths - 1)[:, None]
    logits = layers.head_logits(h_last, params)
    nxt = vocab_parallel_argmax(logits[:, -1], ctx, v_pad, cfg.vocab_size)
    return nxt[:, None], caches


# ---------------------------------------------------------------------------
# Chunked prefill (paged caches)
# ---------------------------------------------------------------------------
def _slot_state(cache: Dict, slot) -> Dict:
    """Slice one slot's row out of a dense per-slot state cache."""
    return jax.tree.map(
        lambda v: lax.dynamic_slice_in_dim(v, slot, 1, axis=0), cache)


def _store_slot_state(cache: Dict, st: Dict, slot) -> Dict:
    return jax.tree.map(
        lambda v, s: lax.dynamic_update_slice_in_dim(v, s.astype(v.dtype),
                                                     slot, axis=0), cache, st)


def _mixer_chunk(kind: str, p: Dict, x: Array, cache: Dict, bt, slot, off,
                 chunk_len, first, ctx, cfg: ModelConfig, li=None):
    if kind == ATTN:
        return attention.gqa_prefill_chunk(p, x, cache, bt, off, chunk_len,
                                           ctx, cfg, li)
    if kind == MLA:
        return attention.mla_prefill_chunk(p, x, cache, bt, off, chunk_len,
                                           ctx, cfg, li)
    # state families: thread the slot's recurrent state across chunks.  The
    # first chunk zeroes it (a freed slot's stale state must not leak into
    # the next admission); lengths are chunk-RELATIVE — rows past chunk_len
    # freeze the state exactly like prompt right-padding.
    lenv = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (x.shape[0],))
    layer_c = _at_layer(cache, li)
    st = _slot_state(layer_c, slot)
    st = jax.tree.map(lambda v: jnp.where(first, jnp.zeros_like(v), v), st)
    if kind == MAMBA:
        y, ns = mamba.mamba_train(p, x, ctx, cfg, with_cache=True,
                                  lengths=lenv, cache=st)
    elif kind == RWKV:
        y, ns = rwkv.rwkv_time_train(p, x, ctx, cfg, with_cache=True,
                                     lengths=lenv, cache=st)
    else:
        raise ValueError(kind)
    return y, _put_layer(cache, _store_slot_state(layer_c, ns, slot), li)


def _ffn_chunk(kind: str, p: Dict, x: Array, cache: Dict, slot, chunk_len,
               first, ctx, cfg: ModelConfig, li=None):
    if kind == DENSE_FFN:
        return ffn.ffn_train(p, x, ctx, cfg.norm_eps), cache
    if kind == MOE_FFN:
        # dropless: rows past chunk_len route too, and cost a real row
        # nothing; their outputs are never read
        return ffn.moe_serve(p, x, ctx, cfg), cache
    lenv = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (x.shape[0],))
    if kind == RWKV:
        layer_c = _at_layer(cache, li)
        st = _slot_state(layer_c, slot)
        st = jax.tree.map(lambda v: jnp.where(first, jnp.zeros_like(v), v), st)
        y, ns = rwkv.rwkv_channel_train(p, x, ctx, cfg, with_cache=True,
                                        lengths=lenv, cache=st)
        return y, _put_layer(cache, _store_slot_state(layer_c, ns, slot), li)
    raise ValueError(kind)


def _block_chunk(kind_pair, lp: Dict, lc: Dict, x: Array, bt, slot, off,
                 chunk_len, first, ctx, cfg, par: ParallelConfig, z3=None,
                 layer=None, li=None):
    """``li``: as in ``_block_decode``."""
    lp = _maybe_gather_zero3(lp, par, z3)
    ctx = ctx.with_layer(layer)
    dy, mc = _mixer_chunk(kind_pair[0], lp["mixer"], x, lc["mixer"], bt, slot,
                          off, chunk_len, first, ctx, cfg, li)
    x = x + dy
    dy, fc = _ffn_chunk(kind_pair[1], lp["ffn"], x, lc["ffn"], slot,
                        chunk_len, first, ctx, cfg, li)
    return x + dy, {"mixer": mc, "ffn": fc}


def prefill_chunk_step(params: Dict, caches: Dict, tokens: Array,
                       block_tables: Array, slot, off, chunk_len,
                       ctx: TPContext, cfg: ModelConfig, par: ParallelConfig,
                       with_logits: bool = False):
    """One fixed-shape chunk of an incremental paged prefill.

    ONE jit program serves every prompt length: tokens is always ``[1, C]``
    (right-padded past ``chunk_len``) and slot/off/chunk_len are traced
    int32 scalars, so admission cost is O(n/C) dispatches of a single
    compiled program — no per-bucket prefill family, no recompiles.

    Chunked prefill always runs the REPLICATED activation layout (like
    decode): a bounded C-row chunk has no sequence-parallel residency to
    win, and dropping SP removes the tp-divisible length constraint.  The
    attention chunk writes K/V through ``block_tables`` BEFORE computing
    scores, so intra-chunk causality and all earlier chunks (including
    REUSED prefix blocks, which are never rewritten) ride the same gathered
    view — results are bit-identical regardless of chunk grouping or reuse.

    Returns (next_token [1,1] — meaningful only on the FINAL chunk, where
    row ``chunk_len-1`` is the prompt's last token — and the new caches),
    and with ``with_logits`` also that row's logits as in ``decode_step``."""
    slot = jnp.asarray(slot, jnp.int32)
    off = jnp.asarray(off, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    first = off == 0
    ctx = ctx.with_layout(False)
    v_pad = pad_vocab(cfg.vocab_size, par.tp)
    x = layers.embed_lookup(params["embed"], tokens, ctx, v_pad)
    x = x.astype(cfg.compute_dtype)

    pat = expanded_pattern(cfg)
    z3 = zero3_flags(cfg, par)
    new_caches: Dict[str, Any] = {"lead": [], "periods": None}
    lead = cfg.leading_dense_layers
    for i in range(lead):
        x, nc = _block_chunk(pat[i], params["lead"][i], caches["lead"][i], x,
                             block_tables, slot, off, chunk_len, first, ctx,
                             cfg, par, z3["lead"][i] if z3["lead"] else None,
                             layer=i)
        new_caches["lead"].append(nc)

    def period_body(x, stacked_p, stacked_c, li):
        ncs = []
        for p_i, kp in enumerate(cfg.pattern):
            x, nc = _block_chunk(kp, stacked_p[p_i], stacked_c[p_i], x,
                                 block_tables, slot, off, chunk_len, first,
                                 ctx, cfg, par,
                                 z3["periods"][p_i] if z3["periods"] else None,
                                 layer=lead + p_i, li=li)
            ncs.append(nc)
        return x, tuple(ncs)

    x, new_caches["periods"] = _scan_periods(
        period_body, x, params["periods"], caches["periods"], n_periods(cfg),
        carried=True)

    h = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    h_last = layers.take_rows(
        h, jnp.broadcast_to(chunk_len - 1, (h.shape[0],)))[:, None]
    logits = layers.head_logits(h_last, params)
    nxt = vocab_parallel_argmax(logits[:, -1], ctx, v_pad, cfg.vocab_size)
    return _step_outputs(nxt, new_caches, logits, with_logits)
