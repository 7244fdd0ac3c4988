"""Shared layers: norms, rotary embeddings, embedding / LM-head seams.

Everything here runs INSIDE ``compat.shard_map`` (see ``repro/compat``).
The residual-stream activation LAYOUT between TP seams is a plan knob
(``ctx.seq_sharded``, resolved from ``SeamPlan.scatter_axis``):

  * sequence-sharded (Megatron-SP, the default): x is [B, S/TP, D] between
    blocks — norms/residual/dropout touch 1/TP of the activation;
  * replicated (classic TP, and ALWAYS the S=1 decode path): x is
    [B, S, D] on every rank.

The vocabulary-parallel embedding + LM head are two of the paper's TP
seams (the LM head's AllGather-GEMM is the single largest GEMM in most of
the assigned archs); the embedding's combining collective follows the same
layout knob (ReduceScatter over sequence vs AllReduce).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.sharding import TPContext

Array = jax.Array


def rms_norm(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def init_rms_norm(d: int, dtype=jnp.bfloat16):
    return jnp.ones((d,), dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 a ln(s) + 1`` (1 when the
    context is not stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(head_dim: int, theta: float, yarn) -> Tuple[int, int]:
    """The frequency slots between which YaRN ramps from extrapolation to
    interpolation: slot ``i`` of ``head_dim / 2`` turns ``r`` times over
    the original context where ``i = d ln(orig / (2 pi r)) / (2 ln theta)``
    (``r`` = ``beta_fast`` for the low end, ``beta_slow`` for the high)."""
    def slot(turns):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(slot(yarn.beta_fast)), 0),
            min(math.ceil(slot(yarn.beta_slow)), head_dim - 1))


def rope_freqs(head_dim: int, theta: float, yarn=None) -> Array:
    """Inverse frequencies of the ``head_dim / 2`` rotary slots; with
    ``yarn`` (``configs.base.YarnConfig``) the slots past the ramp are
    divided by its factor, as DeepSeek-V2's YaRN does."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if yarn is None:
        return freqs
    low, high = yarn_range(head_dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs * (ramp / yarn.factor + (1.0 - ramp))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0,
               yarn=None) -> Array:
    """x: [B, S, H, Dh]; positions: [B, S] absolute token positions.
    ``yarn``: YaRN scaling of the frequencies (and of cos and sin by the
    ratio of its two temperature factors)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta, yarn)                 # [Dh/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B,S,Dh/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: Array, positions_3d: Array, theta: float,
                sections: Tuple[int, int, int] = None) -> Array:
    """Qwen2-VL multimodal RoPE: head_dim/2 freq slots are split into
    (temporal, height, width) sections, each rotated by its own position id.
    positions_3d: [3, B, S].  For pure text all three ids are equal (falls
    back to standard RoPE)."""
    dh = x.shape[-1]
    half = dh // 2
    if sections is None:
        t = half // 2
        hw = (half - t) // 2
        sections = (t, hw, half - t - hw)
    freqs = rope_freqs(dh, theta)                       # [half]
    sec_ids = jnp.concatenate([
        jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)])
    pos = positions_3d[sec_ids]                         # [half, B, S] gathered per slot
    ang = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Vocabulary-parallel embedding (Megatron): table sharded on vocab over TP.
# ---------------------------------------------------------------------------
def embed_lookup(table: Array, tokens: Array, ctx: TPContext,
                 vocab_global: int,
                 scatter_seq: Optional[bool] = None) -> Array:
    """Megatron vocab-parallel embedding.  table: [V/TP, D] local shard;
    tokens: [B, S] REPLICATED over the TP axis.  Out-of-shard tokens
    contribute 0; the combining collective follows the activation layout:
    a ReduceScatter along the sequence (producing the sequence-sharded
    activation directly — the embedding's RS seam) when the residual
    stream is sequence-sharded, a psum (replicated layout / decode)
    otherwise.  ``scatter_seq=None`` resolves from ``ctx.seq_sharded``."""
    if scatter_seq is None:
        scatter_seq = ctx.seq_sharded
    v_loc = table.shape[0]
    start = ctx.tp_index() * v_loc
    local_ids = tokens - start
    in_shard = (local_ids >= 0) & (local_ids < v_loc)
    local_ids = jnp.clip(local_ids, 0, v_loc - 1)
    x = table[local_ids]
    x = jnp.where(in_shard[..., None], x, 0)
    if ctx.axis is not None and ctx.tp > 1:
        if scatter_seq:
            # the embed RS seam rides the plan transport (ring modes:
            # ppermute hops forward AND backward — census-clean)
            x = ctx.scatter_seq(x, "head_ag")
        else:
            with jax.named_scope("seam_embed_ar"):
                x = lax.psum(x, ctx.axis)
    return x


def head_table(params) -> Array:
    """The LM head's vocab-sharded ``[V, D]`` table: the model's own
    ``head`` when it is untied, else the embedding."""
    return params["head"] if "head" in params else params["embed"]


def head_logits(h: Array, params) -> Array:
    """This rank's vocab shard of the next-token logits of the rows of
    ``h`` ([B, S, D] -> [B, S, V/TP]), kept in float32: rounded to bf16,
    logits a rounding apart tie, and the argmax breaks the tie differently
    on each path that computes them."""
    return jnp.einsum("bsd,vd->bsv", h, head_table(params),
                      preferred_element_type=jnp.float32)


def lm_head_logits(x: Array, table: Array, ctx: TPContext) -> Array:
    """x: [B, S/TP, D] -> logits [B, S, V/TP] via the AllGather-GEMM seam.
    (The LM head is the biggest single GEMM: FLUX prologue fusion applies.)"""
    return ctx.op("head_ag")(x, table.T)


def vocab_parallel_xent(logits: Array, labels: Array, ctx: TPContext,
                        vocab_global: int, vocab_real: Optional[int] = None
                        ) -> Array:
    """Cross-entropy over vocab-sharded logits [B, S, V/TP], labels [B, S]
    (full sequence).  Uses the Megatron vocab-parallel log-softmax (psum of
    max and of exp-sums over the TP axis).  Returns per-token loss [B, S].
    ``vocab_real`` masks the padded vocab tail out of the partition function
    (padding stays function-preserving)."""
    v_loc = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if vocab_real is not None and vocab_real < vocab_global:
        col = ctx.tp_index() * v_loc + jnp.arange(v_loc)
        lf = jnp.where(col < vocab_real, lf, -1e30)
    # stability shift only — exact to treat as constant (and pmax has no
    # differentiation rule, so stop the gradient BEFORE it)
    mx = lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    if ctx.axis is not None and ctx.tp > 1:
        mx = lax.pmax(mx, ctx.axis)
    ex = jnp.exp(lf - mx)
    denom = jnp.sum(ex, axis=-1, keepdims=True)
    if ctx.axis is not None and ctx.tp > 1:
        denom = lax.psum(denom, ctx.axis)
    start = ctx.tp_index() * v_loc
    local_ids = labels - start
    in_shard = (local_ids >= 0) & (local_ids < v_loc)
    local_ids = jnp.clip(local_ids, 0, v_loc - 1)
    tgt = jnp.take_along_axis(lf, local_ids[..., None], axis=-1)[..., 0]
    tgt = jnp.where(in_shard, tgt, 0.0)
    if ctx.axis is not None and ctx.tp > 1:
        tgt = lax.psum(tgt, ctx.axis)
    return jnp.log(denom[..., 0]) + mx[..., 0] - tgt


# ---------------------------------------------------------------------------
# Sequence-shard utilities
# ---------------------------------------------------------------------------
def seq_positions(batch: int, s_local: int, ctx: TPContext,
                  offset: int = 0) -> Array:
    """Absolute positions of this device's sequence rows: [B, S_local].
    Sequence-sharded layout adds the shard offset; the replicated layout's
    local rows ARE the global rows."""
    base = offset
    if ctx.seq_sharded:
        base = ctx.tp_index() * s_local + offset
    pos = base + jnp.arange(s_local, dtype=jnp.int32)
    return jnp.broadcast_to(pos, (batch, s_local))


def shift_tokens_right(x: Array, ctx: TPContext) -> Array:
    """x_{t-1} for a (possibly sequence-sharded) [B, S_local, D] tensor:
    shifts within the shard and pulls the boundary column from the left
    neighbor (ppermute of ONE token — the token-shift seam of RWKV).  The
    replicated layout shifts locally (no boundary to exchange)."""
    if ctx.axis is None or ctx.tp == 1 or not ctx.seq_sharded:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    last = x[:, -1:, :]
    n = ctx.tp
    with jax.named_scope("seam_token_shift"):
        # one boundary row between neighbors — the RWKV/mamba token-shift
        # seam, not a transport any FusedOp ring owns
        prev = lax.ppermute(  # lint: allow(raw-collective)
            last, ctx.axis, [(i, (i + 1) % n) for i in range(n)])
    # rank 0's incoming boundary is garbage (wrapped) -> zero it
    is_first = (ctx.tp_index() == 0)
    prev = jnp.where(is_first, jnp.zeros_like(prev), prev)
    return jnp.concatenate([prev, x[:, :-1, :]], axis=1)


# ---------------------------------------------------------------------------
# Per-slot decode-cache utilities (continuous batching)
# ---------------------------------------------------------------------------
def cache_update_rows(cache: Array, new: Array, pos: Array) -> Array:
    """Per-row KV-cache write: ``cache[b, pos[b]:pos[b]+L] = new[b]``.

    cache: [B, S_max, ...]; new: [B, L, ...] (L=1 at decode); pos: [B]
    int32.  Each batch row writes at its OWN position — the continuous-
    batching invariant that slots at staggered sequence positions never
    touch each other's rows."""
    return jax.vmap(
        lambda c, n, p: lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), p, axis=0))(cache, new, pos)


def pool_update_rows(pool: Array, new: Array, bt: Array, start: Array,
                     valid: Optional[Array] = None,
                     layer: Optional[Array] = None) -> Array:
    """Paged KV write THROUGH a block table.

    pool: [N_blocks, bs, ...] physical blocks; new: [B, L, ...]; bt: [B, P]
    int32 per-row block tables; start: [B] int32 logical write offsets
    (row b's new[b, i] lands at logical position start[b] + i, i.e.
    physical block ``bt[b, (start[b]+i) // bs]`` row ``(start[b]+i) % bs``).
    ``valid`` ([B] int32, optional): rows with i >= valid[b] are padding —
    they redirect into physical block 0, the pool's reserved NULL block
    (never allocated, never read unmasked).  Inactive decode slots get the
    same treatment for free: their bt rows are all zeros.  Real rows never
    collide (tables are disjoint and block 0 is never in a table).

    ``layer`` (int32 scalar, optional): ``pool`` is then a layer-STACKED
    leaf ``[layers, N_blocks, bs, ...]`` and the B*L rows scatter straight
    into ``pool[layer, blk, row]`` — no layer is sliced out or rebuilt, so
    a pool carried through the serving programs' layer scan is written in
    place.  Either way the whole (updated) ``pool`` is returned."""
    bs = pool.shape[1 if layer is None else 2]
    b, l = new.shape[0], new.shape[1]
    logical = start[:, None] + jnp.arange(l, dtype=jnp.int32)       # [B, L]
    blk = jnp.take_along_axis(
        bt, jnp.clip(logical // bs, 0, bt.shape[1] - 1), axis=1)
    if valid is not None:
        ok = jnp.arange(l, dtype=jnp.int32)[None, :] < valid[:, None]
        blk = jnp.where(ok, blk, 0)                 # null-block rows
    rows = new.reshape(b * l, *new.shape[2:]).astype(pool.dtype)
    idx = (blk.reshape(-1), (logical % bs).reshape(-1))
    if layer is not None:
        idx = (layer,) + idx
    return pool.at[idx].set(rows)


def pool_view(pool: Array, bt: Array, layer: Optional[Array] = None
              ) -> Array:
    """Gather each row's logical K/V timeline through its block table.

    pool: [N_blocks, bs, ...]; bt: [B, P] -> [B, P*bs, ...].  Logical
    position s of row b reads ``pool[bt[b, s // bs], s % bs]``; positions
    past the row's true length land in stale or null-block rows and MUST
    be masked by the caller (attention masks on pos already do).  With
    ``layer`` (int32 scalar) ``pool`` is a layer-stacked leaf
    ``[layers, N_blocks, bs, ...]`` and the view gathers ``pool[layer, bt]``
    straight from it; the view's shape and contents are the same."""
    g = pool[bt] if layer is None else pool[layer, bt]   # [B, P, bs, ...]
    return g.reshape(bt.shape[0], bt.shape[1] * g.shape[2], *g.shape[3:])


def take_rows(x: Array, idx: Array) -> Array:
    """Per-row gather along the sequence axis: ``x[b, idx[b]]``.

    x: [B, S, ...]; idx: [B] int32 -> [B, ...].  Used to pick each row's
    true last-token entry out of a right-padded batched prefill."""
    idx = jnp.clip(idx, 0, x.shape[1] - 1)
    return jax.vmap(lambda r, i: lax.dynamic_index_in_dim(
        r, i, axis=0, keepdims=False))(x, idx)


def shift_tokens_left(x: Array, ctx: TPContext) -> Array:
    """x_{t+1} for a (possibly sequence-sharded) [B, S_local, D] tensor
    (zero at the end)."""
    if ctx.axis is None or ctx.tp == 1 or not ctx.seq_sharded:
        return jnp.pad(x, ((0, 0), (0, 1), (0, 0)))[:, 1:]
    first = x[:, :1, :]
    n = ctx.tp
    with jax.named_scope("seam_token_shift"):
        nxt = lax.ppermute(  # lint: allow(raw-collective)
            first, ctx.axis, [(i, (i - 1) % n) for i in range(n)])
    is_last = (ctx.tp_index() == n - 1)
    nxt = jnp.where(is_last, jnp.zeros_like(nxt), nxt)
    return jnp.concatenate([x[:, 1:, :], nxt], axis=1)
