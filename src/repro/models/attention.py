"""Attention blocks: GQA (with RoPE / M-RoPE) and DeepSeek MLA.

Train/prefill path (layout per ``ctx.seq_sharded``; Megatron-SP default):
    x[B,S/TP,D] --attn_ag op--> qkv[B,S,local heads] (FLUX prologue seam)
    blocked causal attention (local heads, full sequence)
    attn_out --attn_rs op--> [B,S/TP,D]              (FLUX epilogue seam)
  Replicated layout: the same seams with scatter_axis="hidden" — x stays
  [B,S,D], the AG side is a local GEMM and the RS side an AllReduce.

Decode path (x replicated over TP, batch-sharded over DP):
    local-head QKV projections, KV-cache append, single-token attention,
    output projection via the decode_ar seam (GEMM+AllReduce).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core import overlap
from repro.models import layers
from repro.parallel.sharding import (TPContext, ceil_mult, pad_heads,
                                     pad_kv_heads)

Array = jax.Array


# ---------------------------------------------------------------------------
# Blocked causal attention (pure-jnp flash; differentiable; O(S·block) memory)
# ---------------------------------------------------------------------------
def blocked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                      block_q: int = 512, block_kv: int = 1024,
                      scale: Optional[float] = None) -> Array:
    """q: [B,H,Sq,Dh], k: [B,Hkv,Skv,Dh], v: [B,Hkv,Skv,Dv] (Dv may differ —
    MLA); GQA via head broadcast."""
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    scale = scale or dh ** -0.5
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, dh)

    block_q = min(block_q, sq)
    while sq % block_q:
        block_q //= 2
    block_kv = min(block_kv, skv)
    while skv % block_kv:
        block_kv //= 2
    nq, nkv = sq // block_q, skv // block_kv
    kv_off = skv - sq  # q positions are the suffix of the kv timeline

    qb = qg.reshape(b, hkv, group, nq, block_q, dh)
    kb = k.reshape(b, hkv, nkv, block_kv, dh)
    vb = v.reshape(b, hkv, nkv, block_kv, dv)

    def q_block(qi, qblk):
        # online softmax over kv blocks
        def step(carry, j):
            m, l, acc = carry
            kj = lax.dynamic_index_in_dim(kb, j, axis=2, keepdims=False)
            vj = lax.dynamic_index_in_dim(vb, j, axis=2, keepdims=False)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk.astype(jnp.float32),
                           kj.astype(jnp.float32)) * scale
            if causal:
                qpos = kv_off + qi * block_q + jnp.arange(block_q)
                kpos = j * block_kv + jnp.arange(block_kv)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vj.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((b, hkv, group, block_q, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, group, block_q, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, group, block_q, dv), jnp.float32)
        # causal: kv blocks beyond this q block contribute nothing; still
        # scanned (static shapes) but masked out — remat keeps memory flat.
        (m, l, acc), _ = lax.scan(step, (m0, l0, a0), jnp.arange(nkv))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    outs = [q_block(qi, qb[:, :, :, qi]) for qi in range(nq)]
    out = jnp.stack(outs, axis=3)  # [b,hkv,group,nq,bq,dh]
    return out.reshape(b, hq, sq, dv)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------
class AttnDims(NamedTuple):
    h_pad: int
    hkv_pad: int
    dh: int

    @staticmethod
    def of(cfg: ModelConfig, tp: int) -> "AttnDims":
        return AttnDims(pad_heads(cfg.num_heads, tp),
                        pad_kv_heads(cfg.num_kv_heads, tp),
                        cfg.resolved_head_dim)


def init_gqa(key, cfg: ModelConfig, tp: int, dtype=jnp.bfloat16) -> Dict:
    """Canonical (TP-independent) init, packed into the per-device
    interleaved QKV layout; padded heads are ZERO (function-preserving)."""
    from repro.models import init_utils as iu
    d = AttnDims.of(cfg, tp)
    dm = cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = dm ** -0.5
    wq = (jax.random.normal(k1, (dm, cfg.num_heads * d.dh)) * std)
    wk = (jax.random.normal(k2, (dm, cfg.num_kv_heads * d.dh)) * std)
    wv = (jax.random.normal(k3, (dm, cfg.num_kv_heads * d.dh)) * std)
    wq = iu.interleave_heads(wq, cfg.num_heads, d.dh, tp, d.h_pad)
    wk = iu.replicate_kv_heads(wk, cfg.num_kv_heads, d.dh, tp, d.hkv_pad)
    wv = iu.replicate_kv_heads(wv, cfg.num_kv_heads, d.dh, tp, d.hkv_pad)
    wqkv = iu.pack_qkv(wq, wk, wv, tp)
    wo = (jax.random.normal(k4, (cfg.num_heads * d.dh, dm)) * std)
    wo = iu.zero_pad_rows(wo, d.h_pad * d.dh)
    p = {
        "wqkv": wqkv.astype(dtype),
        "wo": wo.astype(dtype),
        "norm": layers.init_rms_norm(dm, dtype),
    }
    if cfg.qkv_bias:
        p["bqkv"] = jnp.zeros(((d.h_pad + 2 * d.hkv_pad) * d.dh,), dtype)
    return p


def gqa_train(p: Dict, x: Array, ctx: TPContext, cfg: ModelConfig,
              positions_3d: Optional[Array] = None, with_cache: bool = False):
    """x: [B, S/TP, D] -> [B, S/TP, D] (pre-norm residual block body; the
    replicated layout runs [B, S, D] -> [B, S, D] — same seams, hidden
    scatter).  with_cache=True additionally returns the prefill KV cache."""
    tp = ctx.tp
    d = AttnDims.of(cfg, tp)
    hl, hkvl = d.h_pad // tp, d.hkv_pad // tp
    b, s_loc, _ = x.shape
    s = s_loc * ctx.seq_factor

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    # QKV bias rides the AllGather seam's fused epilogue (per chunk in the
    # ring modes, in the tile epilogue for the flux kernel)
    qkv = ctx.op("attn_ag", epilogue=overlap.Epilogue(bias="bqkv" in p))(
        h, p["wqkv"], bias=p.get("bqkv"))
    q, k, v = jnp.split(qkv, [hl * d.dh, (hl + hkvl) * d.dh], axis=-1)
    q = q.reshape(b, s, hl, d.dh)
    k = k.reshape(b, s, hkvl, d.dh)
    v = v.reshape(b, s, hkvl, d.dh)

    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.rope_style == "mrope":
        p3 = positions_3d if positions_3d is not None else \
            jnp.broadcast_to(pos, (3, b, s))
        q = layers.apply_mrope(q, p3, cfg.rope_theta)
        k = layers.apply_mrope(k, p3, cfg.rope_theta)
    elif cfg.rope_style == "rope":
        q = layers.apply_rope(q, pos, cfg.rope_theta, cfg.rope_scaling)
        k = layers.apply_rope(k, pos, cfg.rope_theta, cfg.rope_scaling)

    if ctx.use_kernels:
        # fused flash kernel: K/V stream in bf16 once per q-row block, no
        # fp32 score round-trip (4th §Perf iteration — prefill memory)
        from repro.kernels.flash_attention import flash_attention
        attn = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True)
    else:
        attn = blocked_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3))
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, hl * d.dh)
    out = ctx.op("attn_rs")(attn, p["wo"])
    if with_cache:
        return out, {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
    return out


def gqa_decode(p: Dict, x: Array, cache: Dict, pos: Array, ctx: TPContext,
               cfg: ModelConfig) -> Tuple[Array, Dict]:
    """x: [B, 1, D] replicated over TP; cache: {k,v: [B, S_max, Hkv_l, Dh]}.
    ``pos``: [B] int32 — each row's own write position (continuous batching
    decodes staggered slots in one step).  Returns (out [B,1,D], new cache)."""
    tp = ctx.tp
    d = AttnDims.of(cfg, tp)
    hl, hkvl = d.h_pad // tp, d.hkv_pad // tp
    b = x.shape[0]

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    qkv = jnp.einsum("bsd,df->bsf", h, p["wqkv"])  # local columns; no comm
    if "bqkv" in p:
        qkv = qkv + p["bqkv"]
    q, k, v = jnp.split(qkv, [hl * d.dh, (hl + hkvl) * d.dh], axis=-1)
    q = q.reshape(b, 1, hl, d.dh)
    k = k.reshape(b, 1, hkvl, d.dh)
    v = v.reshape(b, 1, hkvl, d.dh)

    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    pb = pos[:, None]                                    # [B, 1] per-row RoPE
    if cfg.rope_style in ("rope", "mrope"):
        q = layers.apply_rope(q, pb, cfg.rope_theta, cfg.rope_scaling)
        k = layers.apply_rope(k, pb, cfg.rope_theta, cfg.rope_scaling)

    ck = layers.cache_update_rows(cache["k"], k, pos)
    cv = layers.cache_update_rows(cache["v"], v, pos)

    # single-token attention over the cache (memory-bound; roofline's decode
    # bottleneck).  per-row mask: row b attends to positions <= pos[b].
    s_max = ck.shape[1]
    group = hl // hkvl
    qg = q.reshape(b, 1, hkvl, group, d.dh)
    scores = jnp.einsum("bohgd,bshd->bhgos", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (d.dh ** -0.5)
    valid = (jnp.arange(s_max)[None, :] <= pos[:, None])[:, None, None, None, :]
    scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhgos,bshd->bohgd", w, cv.astype(jnp.float32))
    attn = attn.reshape(b, 1, hl * d.dh).astype(x.dtype)

    out = ctx.op("decode_ar")(attn, p["wo"])
    return out, {"k": ck, "v": cv}


def gqa_decode_paged(p: Dict, x: Array, cache: Dict, bt: Array, pos: Array,
                     ctx: TPContext, cfg: ModelConfig, layer=None
                     ) -> Tuple[Array, Dict]:
    """``gqa_decode`` through the paged KV pool: identical math, but K/V
    rows live in shared physical blocks addressed through each slot's
    block table.  cache: {k,v: [N_blocks, bs, Hkv_l, Dh]}; bt: [B, P]
    int32 (inactive slots pass all-zero rows — their writes land in the
    null block and their outputs are discarded by the server).  With
    ``layer`` the leaves are layer-stacked and read/written in place at
    that layer (``layers.pool_update_rows``)."""
    tp = ctx.tp
    d = AttnDims.of(cfg, tp)
    hl, hkvl = d.h_pad // tp, d.hkv_pad // tp
    b = x.shape[0]

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    qkv = jnp.einsum("bsd,df->bsf", h, p["wqkv"])  # local columns; no comm
    if "bqkv" in p:
        qkv = qkv + p["bqkv"]
    q, k, v = jnp.split(qkv, [hl * d.dh, (hl + hkvl) * d.dh], axis=-1)
    q = q.reshape(b, 1, hl, d.dh)
    k = k.reshape(b, 1, hkvl, d.dh)
    v = v.reshape(b, 1, hkvl, d.dh)

    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    pb = pos[:, None]
    if cfg.rope_style in ("rope", "mrope"):
        q = layers.apply_rope(q, pb, cfg.rope_theta, cfg.rope_scaling)
        k = layers.apply_rope(k, pb, cfg.rope_theta, cfg.rope_scaling)

    ck = layers.pool_update_rows(cache["k"], k, bt, pos, layer=layer)
    cv = layers.pool_update_rows(cache["v"], v, bt, pos, layer=layer)
    kview = layers.pool_view(ck, bt, layer)        # [B, P*bs, Hkv_l, Dh]
    vview = layers.pool_view(cv, bt, layer)

    s_tot = kview.shape[1]
    group = hl // hkvl
    qg = q.reshape(b, 1, hkvl, group, d.dh)
    scores = jnp.einsum("bohgd,bshd->bhgos", qg.astype(jnp.float32),
                        kview.astype(jnp.float32)) * (d.dh ** -0.5)
    valid = (jnp.arange(s_tot)[None, :] <= pos[:, None])[:, None, None, None, :]
    scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhgos,bshd->bohgd", w, vview.astype(jnp.float32))
    attn = attn.reshape(b, 1, hl * d.dh).astype(x.dtype)

    out = ctx.op("decode_ar")(attn, p["wo"])
    return out, {"k": ck, "v": cv}


def gqa_prefill_chunk(p: Dict, x: Array, cache: Dict, bt: Array, off,
                      chunk_len, ctx: TPContext, cfg: ModelConfig,
                      layer=None) -> Tuple[Array, Dict]:
    """One fixed-size chunk of an incremental paged prefill.

    x: [B, C, D] REPLICATED (chunked prefill always runs the replicated
    layout — a bounded chunk has no SP residency to win and no tp-divisible
    length constraint); cache: {k,v: [N_blocks, bs, Hkv_l, Dh]} pools;
    bt: [B, P]; off / chunk_len: int32 scalars.  The chunk's K/V rows are
    written through the table FIRST (pad rows past ``chunk_len`` redirect
    to the null block), then scores mask ``kpos <= off + i`` per chunk row
    over the whole gathered view — earlier chunks' and reused prefix
    blocks' K/V participate exactly as in a full prefill, so chunked
    results are independent of the chunk grouping.  ``layer``: as in
    ``gqa_decode_paged``."""
    tp = ctx.tp
    d = AttnDims.of(cfg, tp)
    hl, hkvl = d.h_pad // tp, d.hkv_pad // tp
    b, c_len, _ = x.shape

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    qkv = ctx.op("attn_ag", epilogue=overlap.Epilogue(bias="bqkv" in p))(
        h, p["wqkv"], bias=p.get("bqkv"))
    q, k, v = jnp.split(qkv, [hl * d.dh, (hl + hkvl) * d.dh], axis=-1)
    q = q.reshape(b, c_len, hl, d.dh)
    k = k.reshape(b, c_len, hkvl, d.dh)
    v = v.reshape(b, c_len, hkvl, d.dh)

    off = jnp.asarray(off, jnp.int32)
    qpos = off + jnp.arange(c_len, dtype=jnp.int32)       # absolute positions
    posb = jnp.broadcast_to(qpos, (b, c_len))
    if cfg.rope_style in ("rope", "mrope"):
        q = layers.apply_rope(q, posb, cfg.rope_theta, cfg.rope_scaling)
        k = layers.apply_rope(k, posb, cfg.rope_theta, cfg.rope_scaling)

    offv = jnp.broadcast_to(off, (b,))
    lenv = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))
    ck = layers.pool_update_rows(cache["k"], k, bt, offv, valid=lenv,
                                 layer=layer)
    cv = layers.pool_update_rows(cache["v"], v, bt, offv, valid=lenv,
                                 layer=layer)
    kview = layers.pool_view(ck, bt, layer)
    vview = layers.pool_view(cv, bt, layer)

    s_tot = kview.shape[1]
    group = hl // hkvl
    qg = q.reshape(b, c_len, hkvl, group, d.dh)
    scores = jnp.einsum("bqhgd,bshd->bhgqs", qg.astype(jnp.float32),
                        kview.astype(jnp.float32)) * (d.dh ** -0.5)
    valid = (jnp.arange(s_tot)[None, :] <= qpos[:, None])[None, None, None]
    scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhgqs,bshd->bqhgd", w, vview.astype(jnp.float32))
    attn = attn.reshape(b, c_len, hl * d.dh).astype(x.dtype)
    out = ctx.op("attn_rs")(attn, p["wo"])
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# DeepSeek Multi-head Latent Attention (V3: compressed query; V2-Lite: one
# uncompressed w_q when ``q_lora_rank`` is None)
# ---------------------------------------------------------------------------
def init_mla(key, cfg: ModelConfig, tp: int, dtype=jnp.bfloat16) -> Dict:
    m = cfg.mla
    dm = cfg.d_model
    h_pad = pad_heads(cfg.num_heads, tp)
    dqk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    std = dm ** -0.5
    p = {
        "w_dkv": (jax.random.normal(
            ks[2], (dm, m.kv_lora_rank + m.qk_rope_head_dim)) * std).astype(dtype),
        "w_ukv": (jax.random.normal(
            ks[3], (m.kv_lora_rank,
                    h_pad * (m.qk_nope_head_dim + m.v_head_dim)))
            * m.kv_lora_rank ** -0.5).astype(dtype),
        "w_o": (jax.random.normal(ks[4], (h_pad * m.v_head_dim, dm))
                * std).astype(dtype),
        "kv_norm": layers.init_rms_norm(m.kv_lora_rank, dtype),
        "norm": layers.init_rms_norm(dm, dtype),
    }
    if m.q_lora_rank is None:
        p["w_q"] = (jax.random.normal(ks[0], (dm, h_pad * dqk))
                    * std).astype(dtype)
        return p
    p["w_dq"] = (jax.random.normal(ks[0], (dm, m.q_lora_rank))
                 * std).astype(dtype)
    p["w_uq"] = (jax.random.normal(ks[1], (m.q_lora_rank, h_pad * dqk))
                 * m.q_lora_rank ** -0.5).astype(dtype)
    p["q_norm"] = layers.init_rms_norm(m.q_lora_rank, dtype)
    return p


def _local_proj(a: Array, w: Array) -> Array:
    return jnp.einsum("bsd,df->bsf", a, w)


def _mla_query(p: Dict, h: Array, cfg: ModelConfig, up=_local_proj) -> Array:
    """Every local head's query ``[B, S, H_l * (nope + rope)]`` from the
    normed input: one ``w_q`` projection, or the compressed path
    (``w_dq``, ``q_norm``, ``w_uq``).  ``up`` runs the projection onto the
    heads: the AllGather seam in training, a local GEMM otherwise."""
    if "w_q" in p:
        return up(h, p["w_q"])
    q_lat = layers.rms_norm(_local_proj(h, p["w_dq"]), p["q_norm"],
                            cfg.norm_eps)
    return up(q_lat, p["w_uq"])


def _mla_latent(p: Dict, h: Array, cfg: ModelConfig, positions: Array
                ) -> Tuple[Array, Array]:
    """The cached latent of each row: the normed ``kv_lora_rank`` part and
    the shared rope key, rotated at ``positions`` ([B, S])."""
    r = cfg.mla.kv_lora_rank
    kv_all = _local_proj(h, p["w_dkv"])
    kv_lat = layers.rms_norm(kv_all[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = layers.apply_rope(kv_all[..., None, r:], positions,
                               cfg.rope_theta, cfg.rope_scaling)[..., 0, :]
    return kv_lat, k_rope


def latent_width(cfg: ModelConfig) -> int:
    """Lanes of a latent cache row: the latent, the shared rope key, and
    zeros up to a multiple of 128.  The TPU stores a pool leaf whose minor
    dimension is not a multiple of 128 block-minor (a 64-wide rope leaf of
    its own, or DeepSeek's 576-wide row), and every program that scatters
    into or gathers from it then relays the whole layer stack out and
    back; a 640-wide row stays row-major, for 11% more bytes."""
    m = cfg.mla
    return ceil_mult(m.kv_lora_rank + m.qk_rope_head_dim, 128)


def _latent_row(kv_lat: Array, k_rope: Array, cfg: ModelConfig) -> Array:
    """A cache row (``latent_width`` lanes) of each token."""
    row = jnp.concatenate([kv_lat, k_rope.astype(kv_lat.dtype)], axis=-1)
    pad = latent_width(cfg) - row.shape[-1]
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])


def _split_latent(rows: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    """The latent and the rope key of cache rows."""
    m = cfg.mla
    return (rows[..., :m.kv_lora_rank],
            rows[..., m.kv_lora_rank:m.kv_lora_rank + m.qk_rope_head_dim])


def _mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``(nope + rope)^-0.5``, times YaRN's temperature
    factor squared where the configuration has one."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= layers.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _absorbed_query(p: Dict, h: Array, cfg: ModelConfig, positions: Array,
                    hl: int) -> Tuple[Array, Array, Array]:
    """The absorbed-form query: ``q_nope . W_uk`` per head in the latent
    space ([B, S, H_l, rank], fp32), the rotated rope query, and ``W_uv``
    ([rank, H_l, v]) for after the weighted latent sum."""
    m = cfg.mla
    b, s = h.shape[:2]
    q = _mla_query(p, h, cfg).reshape(
        b, s, hl, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
    w_ukv = p["w_ukv"].reshape(m.kv_lora_rank, hl,
                               m.qk_nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[:, :, :m.qk_nope_head_dim]             # [r, h, dn]
    w_uv = w_ukv[:, :, m.qk_nope_head_dim:]             # [r, h, dv]
    q_eff = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    return q_eff, q_rope, w_uv


def _latent_attention(q_eff: Array, q_rope: Array, c: Array, kr: Array,
                      valid: Array, scale: float) -> Array:
    """Scores of the absorbed queries against every cached latent row
    (``c``: [B, S, rank], ``kr``: [B, S, rope]) masked by ``valid``
    (broadcast to [B, H, Q, S]); returns the weighted latent sum
    [B, Q, H, rank] in fp32."""
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_eff, c.astype(jnp.float32))
              + jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                           kr.astype(jnp.float32))) * scale
    w = jax.nn.softmax(jnp.where(valid, scores, -1e30), axis=-1)
    return jnp.einsum("bhqs,bsr->bqhr", w, c.astype(jnp.float32))


def _latent_out(ctx_lat: Array, w_uv: Array, w_o: Array, dtype,
                ctx: TPContext, seam: str) -> Array:
    """``W_uv`` after the weighted latent sum, then the output seam."""
    b, s, hl = ctx_lat.shape[:3]
    attn = jnp.einsum("bqhr,rhd->bqhd", ctx_lat, w_uv.astype(jnp.float32))
    attn = attn.reshape(b, s, hl * w_uv.shape[-1]).astype(dtype)
    return ctx.op(seam)(attn, w_o)


def mla_train(p: Dict, x: Array, ctx: TPContext, cfg: ModelConfig,
              with_cache: bool = False):
    m = cfg.mla
    tp = ctx.tp
    h_pad = pad_heads(cfg.num_heads, tp)
    hl = h_pad // tp
    b, s_loc, _ = x.shape
    s = s_loc * ctx.seq_factor
    dqk = m.qk_nope_head_dim + m.qk_rope_head_dim

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    # latent down-projection: replicated weights, sequence-local compute;
    # RoPE on the shard (positions known locally)
    kv_lat, k_rope_s = _mla_latent(p, h, cfg,
                                   layers.seq_positions(b, s_loc, ctx))

    # head up-projections: the FLUX AllGather-GEMM seams (distinct input
    # latents -> no gather sharing between them)
    ag_op = ctx.op("attn_ag")
    q = _mla_query(p, h, cfg, up=ag_op).reshape(b, s, hl, dqk)
    kv = ag_op(kv_lat, p["w_ukv"])
    kv = kv.reshape(b, s, hl, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)

    # the shared rope key is a non-GEMM seam payload: it rides the seam's
    # ring transport (no standalone all_gather; no-op when replicated)
    k_rope = ctx.gather_seq(k_rope_s, "attn_ag")
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = layers.apply_rope(q_rope, pos, cfg.rope_theta, cfg.rope_scaling)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, hl, m.qk_rope_head_dim))], axis=-1)

    with jax.named_scope("mla_attn"):
        attn = blocked_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3),
                                 scale=_mla_scale(cfg))
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, hl * m.v_head_dim)
    out = ctx.op("attn_rs")(attn, p["w_o"])
    if with_cache:
        c_full = ctx.gather_seq(kv_lat, "attn_ag")
        return out, {"latent": _latent_row(c_full, k_rope, cfg).astype(
            jnp.bfloat16)}
    return out


def mla_decode(p: Dict, x: Array, cache: Dict, pos: Array, ctx: TPContext,
               cfg: ModelConfig) -> Tuple[Array, Dict]:
    """Absorbed-form MLA decode: the KV cache stores only the latent
    (kv_lora_rank + rope) per token — DeepSeek's decode memory win.  The
    nope-scores absorb W_uk into the query; values absorb W_uv after the
    weighted latent sum.  ``pos``: [B] int32 per-row write positions."""
    hl = pad_heads(cfg.num_heads, ctx.tp) // ctx.tp
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    pb = pos[:, None]                                    # [B, 1] per-row RoPE

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    kv_lat, k_rope = _mla_latent(p, h, cfg, pb)
    q_eff, q_rope, w_uv = _absorbed_query(p, h, cfg, pb, hl)
    lat = layers.cache_update_rows(cache["latent"],
                                   _latent_row(kv_lat, k_rope, cfg), pos)
    c_cache, r_cache = _split_latent(lat, cfg)

    with jax.named_scope("mla_attn"):
        if ctx.use_kernels:
            # fused flash-style pass over the latent cache: ONE streaming
            # read instead of two + no fp32 score materialization
            from repro.kernels.mla_decode import mla_decode_attention
            ctx_lat = mla_decode_attention(
                q_eff[:, 0], q_rope[:, 0].astype(jnp.float32), c_cache,
                r_cache, pos + 1, scale=_mla_scale(cfg))[:, None]
        else:
            valid = (jnp.arange(c_cache.shape[1])[None, :]
                     <= pos[:, None])[:, None, None, :]
            ctx_lat = _latent_attention(q_eff, q_rope, c_cache, r_cache,
                                        valid, _mla_scale(cfg))
    out = _latent_out(ctx_lat, w_uv, p["w_o"], x.dtype, ctx, "decode_ar")
    return out, {"latent": lat}


def mla_decode_paged(p: Dict, x: Array, cache: Dict, bt: Array, pos: Array,
                     ctx: TPContext, cfg: ModelConfig, layer=None
                     ) -> Tuple[Array, Dict]:
    """Absorbed-form MLA decode over the paged latent pool.  cache:
    {latent: [N_blocks, bs, latent_width]}; bt: [B, P].
    The gathered per-row views are shaped like the dense caches, so the
    fused decode kernel path applies unchanged.  ``layer``: as in
    ``gqa_decode_paged``."""
    hl = pad_heads(cfg.num_heads, ctx.tp) // ctx.tp
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    pb = pos[:, None]

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    kv_lat, k_rope = _mla_latent(p, h, cfg, pb)
    q_eff, q_rope, w_uv = _absorbed_query(p, h, cfg, pb, hl)
    pool = layers.pool_update_rows(cache["latent"],
                                   _latent_row(kv_lat, k_rope, cfg), bt,
                                   pos, layer=layer)
    cview, rview = _split_latent(layers.pool_view(pool, bt, layer), cfg)

    with jax.named_scope("mla_attn"):
        if ctx.use_kernels:
            from repro.kernels.mla_decode import mla_decode_attention
            ctx_lat = mla_decode_attention(
                q_eff[:, 0], q_rope[:, 0].astype(jnp.float32), cview, rview,
                pos + 1, scale=_mla_scale(cfg))[:, None]
        else:
            valid = (jnp.arange(cview.shape[1])[None, :]
                     <= pos[:, None])[:, None, None, :]
            ctx_lat = _latent_attention(q_eff, q_rope, cview, rview, valid,
                                        _mla_scale(cfg))
    out = _latent_out(ctx_lat, w_uv, p["w_o"], x.dtype, ctx, "decode_ar")
    return out, {"latent": pool}


def mla_prefill_chunk(p: Dict, x: Array, cache: Dict, bt: Array, off,
                      chunk_len, ctx: TPContext, cfg: ModelConfig,
                      layer=None) -> Tuple[Array, Dict]:
    """Absorbed-form chunked prefill over the paged latent pool: the same
    math as ``mla_decode_paged`` with C query rows at a time (scores are
    identical to the non-absorbed prefill by associativity — q_nope·(W_uk c)
    = (q_nope W_uk)·c, both in fp32).  x: [B, C, D] replicated.
    ``layer``: as in ``gqa_decode_paged``."""
    hl = pad_heads(cfg.num_heads, ctx.tp) // ctx.tp
    b, c_len, _ = x.shape
    off = jnp.asarray(off, jnp.int32)
    qpos = off + jnp.arange(c_len, dtype=jnp.int32)
    posb = jnp.broadcast_to(qpos, (b, c_len))

    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    kv_lat, k_rope = _mla_latent(p, h, cfg, posb)
    q_eff, q_rope, w_uv = _absorbed_query(p, h, cfg, posb, hl)

    offv = jnp.broadcast_to(off, (b,))
    lenv = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))
    pool = layers.pool_update_rows(cache["latent"],
                                   _latent_row(kv_lat, k_rope, cfg), bt,
                                   offv, valid=lenv, layer=layer)
    cview, rview = _split_latent(layers.pool_view(pool, bt, layer), cfg)

    with jax.named_scope("mla_attn"):
        valid = (jnp.arange(cview.shape[1])[None, :]
                 <= qpos[:, None])[None, None]
        ctx_lat = _latent_attention(q_eff, q_rope, cview, rview, valid,
                                    _mla_scale(cfg))
    out = _latent_out(ctx_lat, w_uv, p["w_o"], x.dtype, ctx, "attn_rs")
    return out, {"latent": pool}
