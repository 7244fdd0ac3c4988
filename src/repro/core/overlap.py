"""FLUX-style communication/computation overlap ops (the paper's core).

The public surface is ONE declarative op object::

    FusedOp(kind="ag"|"rs"|"ar", axis=..., mode=..., comm_chunks=...,
            reverse=..., blocks=..., epilogue=Epilogue(...), n_weights=N,
            fuse_epilogue=True, shared_gather=True, scatter_axis="seq")

    op(x, *weights, bias=..., scale=..., residual=...) -> Array | tuple

``kind`` names the TP seam collective and ``scatter_axis`` the activation
LAYOUT the seam consumes/produces (paper Fig. 2 shapes; Megatron-SP vs
plain TP):

  scatter_axis="seq"  — the residual stream is SEQUENCE-SHARDED between
  seams ([B, S/N, D]); norms/residual/dropout between seams run on 1/N of
  the activation:

    ag   x[B, S/N, D] , w[D, F/N]  ->  (AllGather S) @ w  = y[B, S, F/N]
    rs   y[B, S, F/N] , w[F/N, D]  ->  ReduceScatter_S(y @ w) = [B, S/N, D]

  scatter_axis="hidden" — the residual stream stays REPLICATED ([B, S, D]);
  the only sharding between the paired seams is the hidden dim of the
  intermediate y, so the AG side needs NO collective (x is already full)
  and the RS side degenerates to GEMM + AllReduce:

    ag   x[B, S, D]   , w[D, F/N]  ->  x @ w               = y[B, S, F/N]
    rs   y[B, S, F/N] , w[F/N, D]  ->  AllReduce(y @ w)    = [B, S, D]

  ar   y[B, m, F/N] , w[F/N, D]  ->  AllReduce(y @ w)       = [B, m, D]
       (decode path: m == 1 new token — "ar" IS the hidden layout and
       always coerces scatter_axis="hidden")

  a2a  x[EP, E/EP, C, D], (w1, w3, w2)[E/EP, ...]  ->  out[EP, E/EP, C, D]
       the MoE expert-parallel token exchange: ``x[j]`` holds the
       capacity-bucketed tokens this rank routes to EP rank j's local
       experts; ``out[j] = E_j(x[j])`` returns them expert-processed.
       ``axis`` is the EP axis TUPLE (possibly multi-axis, e.g.
       ``("data", "model")`` under ep_over_dp; rank order is axis-major).
       Dispatch AND combine ride per-shift ppermute chunks interleaved
       with the per-local-expert gated GEMMs (w1/w3/w2 compute on chunk i
       hides the transfer of chunk i+1); ``xla*`` modes run the two
       barrier ``lax.all_to_all`` exchanges instead.  Epilogue must be the
       pure ``gate="pair"`` spec (silu(x@w1) * (x@w3) @ w2).

  Total comm volume per layer is layout-invariant (AG+RS over seq ==
  one AllReduce), but "seq" keeps 1/N of the activation resident between
  seams — the knob the autotuner sweeps via ``SeamPlan.scatter_axis``.

``mode`` selects the transport (``VALID_MODES``): ``xla`` is the
non-overlapping baseline, ``decomposed`` the chunked ``ppermute`` ring
(``comm_chunks`` = the paper's §4.3 communication tile size, ``reverse``
the pull/push ring direction), ``decomposed_bidir`` counter-rotating
half-rings, and ``flux`` the paper's fused Pallas kernels
(``repro/kernels/``).

``wire_dtype`` (orthogonal to ``mode``) quantizes the FORWARD wire:
``None`` ships the native dtype; ``"int8"`` / ``"fp8_e4m3"`` /
``"int4"`` (packed two nibbles per byte) block-quantize every hop's
payload with per-128-block float32 scales (Flash-Communication-style).
Quantization is forward-only — cotangents always ride the
full-precision transports, so grads are bitwise those of the fp wire.
``flux`` kernels have no quantized DMA path (``wire_dtype`` with
``mode="flux"`` raises); ``xla`` reductions (psum / psum_scatter)
cannot carry mixed-scale payloads, so ``rs``/``ar`` ignore
``wire_dtype`` under ``mode="xla"``.  The legacy ``*_q8`` mode
spellings normalize to ``(base mode, wire_dtype="int8")``.

What makes the op *fused* (paper thesis: push neighboring compute into the
communication loop):

  * ``epilogue`` — a small declarative spec (bias add / activation /
    gate-multiply / residual add / dequant scale).  On the ring transports
    the epilogue is applied PER CHUNK inside the overlapped loop
    (``fuse_epilogue=True``); the flux kernels apply bias+activation in the
    tile epilogue.  ``rs``/``ar`` epilogues run on the reduced output
    (residual adds fuse into the seam's tail).
  * ``n_weights`` — multi-weight AllGather ops share ONE ring pass for N
    weight GEMMs (gather once, multiply N times): the gated-FFN w1/w3 pair
    rides a single AllGather instead of two, halving ring traffic
    (``shared_gather=True``; ``False`` restores one ring per weight — a
    plan-visible autotuner knob, like ``fuse_epilogue``).

``custom_vjp`` is defined ONCE at the ``FusedOp`` level: the backward pass
is the *interchanged* overlapped op (AG <-> RS, paper §2.1) applied to the
epilogue-transposed cotangent, and multi-weight ops share one backward ring
too (dX = RS(sum_i dY_i @ W_i^T) in a single ring pass) plus one activation
re-gather for all dW_i.

All ops must be called inside ``compat.shard_map``; ``axis`` names the TP
mesh axis.  Model code never builds a ``FusedOp`` by hand — it resolves one
through the plan registry: ``ctx.op(seam, epilogue=..., n_weights=...,
scatter_axis=...)`` (i.e. ``ctx.plans.resolve(seam).op(...)``), so "what is
fused" AND "which layout the seam emits" are per-seam ``SeamPlan`` knobs
the autotuner sweeps, not call-site constants.

Non-GEMM sequence payloads that must cross a seam (MLA's shared rope key,
cache tails) ride :func:`gather_seq` — the same ppermute ring transport —
so no standalone full-activation ``all_gather`` remains between seams.

(The pre-FusedOp ``ag_matmul`` / ``matmul_rs`` / ``matmul_ar`` wrappers
finished their one-release deprecation window and are gone.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import compat

Array = jax.Array

VALID_MODES = ("xla", "decomposed", "flux", "decomposed_bidir")

VALID_KINDS = ("ag", "rs", "ar", "a2a")

# Low-precision wire transports (module docstring): quantize each hop's
# payload with per-128-block scales; forward-only — the backward pass
# always rides the full-precision transports.
VALID_WIRE_DTYPES = (None, "int8", "fp8_e4m3", "int4")

# The pre-wire_dtype spellings ("xla" / "decomposed" + the q8 suffix) keep
# loading for one deprecation window: they normalize to the base mode with
# wire_dtype="int8".  Built by concatenation so the deprecated-q8-mode lint
# rule has no literal to flag here.
_DEPRECATED_Q8_SUFFIX = "_q8"
_DEPRECATED_Q8_MODES = {m + _DEPRECATED_Q8_SUFFIX: m
                        for m in ("xla", "decomposed")}


def normalize_mode(mode: str, wire_dtype: Optional[str] = None):
    """``(mode, wire_dtype)`` with deprecated ``*_q8`` spellings mapped to
    the base mode + ``wire_dtype="int8"`` (an explicit wire_dtype wins)."""
    base = _DEPRECATED_Q8_MODES.get(mode)
    if base is not None:
        return base, (wire_dtype if wire_dtype is not None else "int8")
    return mode, wire_dtype

# Every collective this module emits is wrapped in a ``jax.named_scope``
# whose name starts with this prefix.  The scope lands on the traced eqn's
# ``source_info.name_stack`` (surviving jvp/transpose wrapping, scan bodies
# and custom_vjp backward rules), which is how ``repro.analysis.seamcheck``
# attributes ring collectives to their owning seam: any full-activation
# collective WITHOUT a seam scope in a traced step is a census violation.
SEAM_SCOPE_PREFIX = "seam"


def _seam_scope(name: str):
    """Provenance marker for one seam-owned collective transport."""
    return jax.named_scope(f"{SEAM_SCOPE_PREFIX}_{name}")

# activation layout a seam consumes/produces (module docstring):
#   "seq"    — sequence-sharded residual stream (Megatron-SP)
#   "hidden" — replicated residual stream; only the intermediate's hidden
#              dim is sharded (classic TP; the decode layout)
VALID_SCATTER_AXES = ("seq", "hidden")


def _axis_size(axis: Optional[str]) -> int:
    if axis is None:
        return 1
    return compat.axis_size(axis)


# ---------------------------------------------------------------------------
# Epilogue: the declarative "what is fused after the GEMM" spec
# ---------------------------------------------------------------------------
def _sqrelu(v):
    return jnp.square(jax.nn.relu(v))


ACTIVATIONS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
               "relu": jax.nn.relu, "sqrelu": _sqrelu}


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Elementwise tail fused into a ``FusedOp``.

    Application order (z starts as the first GEMM/collective output)::

        z = z * scale          (scale=True;   per-column dequant multiply)
        z = z + bias           (bias=True;    broadcast over rows)
        gate == "pair" : z = act(z) * y2     (second weight's output)
        gate == "split": z = act(a) * b      (a, b = split(z, 2, axis=-1))
        else           : z = act(z)          (activation set)
        z = z + residual       (residual=True)

    Flags declare the SHAPE of the fusion (static, hashable — part of the
    op's trace key); the operand ARRAYS (bias / scale / residual) are passed
    at call time and participate in autodiff.
    """
    bias: bool = False
    activation: Optional[str] = None          # ACTIVATIONS key
    gate: Optional[str] = None                # None | "pair" | "split"
    residual: bool = False
    scale: bool = False

    def __post_init__(self):
        if self.activation is not None and self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r}")
        if self.gate not in (None, "pair", "split"):
            raise ValueError(f"unknown gate {self.gate!r}")

    @property
    def is_identity(self) -> bool:
        return not (self.bias or self.activation or self.gate
                    or self.residual or self.scale)

    def apply(self, ys: Sequence[Array], bias=None, scale=None,
              residual=None) -> Array:
        z = ys[0]
        if self.scale:
            z = z * scale
        if self.bias:
            z = z + bias
        act = ACTIVATIONS[self.activation] if self.activation else (lambda v: v)
        if self.gate == "pair":
            z = act(z) * ys[1]
        elif self.gate == "split":
            a, b = jnp.split(z, 2, axis=-1)
            z = act(a) * b
        elif self.activation:
            z = act(z)
        if self.residual:
            z = z + residual
        return z


# ---------------------------------------------------------------------------
# Ring transports, generalized over an arbitrary per-chunk compute
# ---------------------------------------------------------------------------
def _ring_perm(axis: str, reverse: bool = False):
    n = compat.axis_size(axis)
    if reverse:
        return [(i, (i - 1) % n) for i in range(n)]
    return [(i, (i + 1) % n) for i in range(n)]


def _ring_gather(x: Array, axis: str, reverse: bool = False) -> Array:
    """Sequence AllGather implemented as a ppermute ring (shard-exact, same
    assembly order as ``lax.all_gather(tiled=True)``): the transport every
    seam-adjacent gather rides so no standalone collective appears between
    seams.  Gathers along dim -2."""
    n = compat.axis_size(axis)
    me = lax.axis_index(axis)
    s_shard = x.shape[-2]
    out = jnp.zeros((*x.shape[:-2], s_shard * n, x.shape[-1]), x.dtype)
    buf = x
    with _seam_scope("ring_gather"):
        for step in range(n):
            owner = (me + step) % n if reverse else (me - step) % n
            out = lax.dynamic_update_slice_in_dim(out, buf, owner * s_shard,
                                                  axis=out.ndim - 2)
            if step < n - 1:
                buf = lax.ppermute(buf, axis, _ring_perm(axis, reverse))
    return out


def gather_seq(x: Array, axis: Optional[str], mode: str = "decomposed",
               reverse: bool = False) -> Array:
    """Gather a sequence-sharded non-GEMM payload (rope keys, cache tails,
    boundary rows) to full length along dim -2.

    ``mode`` follows the seam plan's transport family: the ring modes ride
    ppermute hops (census-clean: no standalone ``all_gather`` in the
    jaxpr), ``xla*`` uses the monolithic collective.  Values are identical
    either way."""
    if axis is None or _axis_size(axis) == 1:
        return x
    if mode.startswith("decomposed"):
        return _ring_gather(x, axis, reverse)
    with _seam_scope("gather_seq"):
        return lax.all_gather(x, axis, axis=x.ndim - 2, tiled=True)


def scatter_seq_sum(x: Array, axis: Optional[str], mode: str = "decomposed",
                    reverse: bool = False) -> Array:
    """ReduceScatter along dim -2 of a per-rank full-sequence partial (the
    embedding seam's combining collective under the sequence-sharded
    layout): out[rows of my shard] = sum over ranks of x[those rows].

    The ring modes ride ppermute hops (same accumulation order as
    ``_rs_ring``), so BOTH directions of the embed seam stay census-clean:
    the autodiff transpose of the ppermute/slice chain is a ppermute ring
    gather, not a monolithic ``all_gather``."""
    if axis is None or _axis_size(axis) == 1:
        return x
    if not mode.startswith("decomposed"):
        with _seam_scope("scatter_seq"):
            return lax.psum_scatter(x, axis, scatter_dimension=x.ndim - 2,
                                    tiled=True)
    n = compat.axis_size(axis)
    me = lax.axis_index(axis)
    s_shard = x.shape[-2] // n

    def owner_at(s):
        return ((me - (n - 1 - s)) % n if reverse
                else (me + n - 1 - s) % n)

    def part(s):
        return lax.dynamic_slice_in_dim(x, owner_at(s) * s_shard, s_shard,
                                        axis=x.ndim - 2)

    with _seam_scope("scatter_seq"):
        acc = part(0)
        for s in range(1, n):
            acc = lax.ppermute(acc, axis, _ring_perm(axis, reverse))
            acc = acc + part(s)
    return acc


def _sub_chunks(s_shard: int, n: int, comm_chunks: int) -> int:
    sub = max(1, comm_chunks // n) if comm_chunks else 1
    sub = min(sub, s_shard)
    while s_shard % sub:
        sub -= 1
    return sub


def _out_buffers(x: Array, seq_len: int, chunk_len: int,
                 chunk_fn: Callable) -> list:
    """Zero output buffers sized from the chunk_fn's abstract output."""
    probe = jax.ShapeDtypeStruct((*x.shape[:-2], chunk_len, x.shape[-1]),
                                 x.dtype)
    shapes = jax.eval_shape(chunk_fn, probe)
    return [jnp.zeros((*x.shape[:-2], seq_len, sh.shape[-1]), sh.dtype)
            for sh in shapes]


def _ag_ring(x: Array, axis: str, comm_chunks: int, reverse: bool,
             chunk_fn: Callable, encode=None, decode=None) -> Tuple[Array, ...]:
    """Chunked AllGather ring of shard hops: each landed chunk is consumed by
    ``chunk_fn`` ([..., L, D] -> tuple of [..., L, W_b]) as soon as it
    arrives, so the chunk GEMMs (and any fused epilogue) overlap with the
    hops.  ``encode``/``decode`` optionally transform the ring payload
    (int8 block quantization); the GEMM always sees the decoded chunk.
    Ring order starts at the LOCAL shard (paper §4.3)."""
    n = compat.axis_size(axis)
    me = lax.axis_index(axis)
    s_shard = x.shape[-2]
    sub = _sub_chunks(s_shard, n, comm_chunks)
    sub_len = s_shard // sub

    payloads = encode(x) if encode else (x,)
    pieces = [jnp.split(p, sub, axis=-2) if sub > 1 else [p]
              for p in payloads]
    bufs = [tuple(pieces[pi][j] for pi in range(len(payloads)))
            for j in range(sub)]

    ys = _out_buffers(x, s_shard * n, sub_len, chunk_fn)
    with _seam_scope("ag_ring"):
        for step in range(n):
            # step 0 consumes the LOCAL shard ("local signals preset to
            # true"); later steps consume the shard arriving from the
            # neighbor.
            owner = (me + step) % n if reverse else (me - step) % n
            for j, buf in enumerate(bufs):
                piece = decode(buf) if decode else buf[0]
                chunks = chunk_fn(piece)
                start = owner * s_shard + j * sub_len
                for b, ch in enumerate(chunks):
                    ys[b] = lax.dynamic_update_slice_in_dim(
                        ys[b], ch, start, axis=ys[b].ndim - 2)
            if step < n - 1:
                bufs = [tuple(lax.ppermute(p, axis, _ring_perm(axis, reverse))
                              for p in buf) for buf in bufs]
    return tuple(ys)


def _ag_bidir(x: Array, axis: str, comm_chunks: int, chunk_fn: Callable,
              encode=None, decode=None) -> Tuple[Array, ...]:
    """Counter-rotating half-rings (beyond-paper): ICI torus links are
    full-duplex PER DIRECTION, so two opposite half-volume rings halve the
    per-link traffic (~2x on ring-bound seams).  ``encode``/``decode``
    transform each half-ring's payload like ``_ag_ring``'s hooks."""
    n = compat.axis_size(axis)
    me = lax.axis_index(axis)
    s_shard = x.shape[-2]
    half = s_shard // 2
    if half == 0 or s_shard % 2:
        return _ag_ring(x, axis, comm_chunks, False, chunk_fn,
                        encode=encode, decode=decode)
    lo, hi = jnp.split(x, 2, axis=-2)          # top rides right, bottom left

    ys = _out_buffers(x, s_shard * n, half, chunk_fn)
    buf_r = encode(lo) if encode else (lo,)
    buf_l = encode(hi) if encode else (hi,)
    with _seam_scope("ag_bidir"):
        for step in range(n):
            owner_r = (me - step) % n
            owner_l = (me + step) % n
            cr = chunk_fn(decode(buf_r) if decode else buf_r[0])
            cl = chunk_fn(decode(buf_l) if decode else buf_l[0])
            for b in range(len(ys)):
                ys[b] = lax.dynamic_update_slice_in_dim(
                    ys[b], cr[b], owner_r * s_shard, axis=ys[b].ndim - 2)
                ys[b] = lax.dynamic_update_slice_in_dim(
                    ys[b], cl[b], owner_l * s_shard + half,
                    axis=ys[b].ndim - 2)
            if step < n - 1:
                buf_r = tuple(lax.ppermute(p, axis, _ring_perm(axis))
                              for p in buf_r)
                buf_l = tuple(lax.ppermute(p, axis,
                                           _ring_perm(axis, reverse=True))
                              for p in buf_l)
    return tuple(ys)


# ---------------------------------------------------------------------------
# wire_dtype: block-quantized wire codecs (beyond-paper knob)
# ---------------------------------------------------------------------------
_WIRE_BLOCK = 128
_Q8_BLOCK = _WIRE_BLOCK

# symmetric range of each wire dtype (the block scale is amax / qmax)
_WIRE_QMAX = {"int8": 127.0, "fp8_e4m3": 448.0, "int4": 7.0}


def wire_encode(x: Array, wire_dtype: str) -> Tuple[Array, Array]:
    """``(q, scale)`` payload pair for one wire hop: per-128-block absmax
    scales (float32), values quantized to the wire dtype.  ``int4`` packs
    two sign-extended nibbles per uint8 when the feature dim is even
    (decode detects packing by dtype).  All-zero blocks clamp the scale
    away from zero so they decode to exact zeros, never NaN."""
    qmax = _WIRE_QMAX[wire_dtype]
    d = x.shape[-1]
    blocks = d // _WIRE_BLOCK if d % _WIRE_BLOCK == 0 else 1
    xb = x.reshape(*x.shape[:-1], blocks, d // blocks).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / qmax, jnp.finfo(jnp.float32).tiny)
    v = xb / scale
    if wire_dtype == "int8":
        q = jnp.clip(jnp.round(v), -127, 127).astype(jnp.int8)
        q = q.reshape(*x.shape)
    elif wire_dtype == "fp8_e4m3":
        q = v.astype(jnp.float8_e4m3fn).reshape(*x.shape)
    elif wire_dtype == "int4":
        q4 = jnp.clip(jnp.round(v), -7, 7).astype(jnp.int8).reshape(*x.shape)
        q = _int4_pack(q4)
    else:
        raise ValueError(f"invalid wire_dtype {wire_dtype!r}")
    return q, scale[..., 0].astype(jnp.float32)


def wire_decode(payloads: Sequence[Array], wire_dtype: str, dtype) -> Array:
    """Inverse of :func:`wire_encode` on a ``(q, scale)`` payload pair."""
    q, scale = payloads
    if wire_dtype == "int4" and q.dtype == jnp.uint8:
        q = _int4_unpack(q)
    d = q.shape[-1]
    blocks = scale.shape[-1]
    xb = q.astype(jnp.float32).reshape(*q.shape[:-1], blocks, d // blocks)
    return (xb * scale[..., None]).reshape(*q.shape).astype(dtype)


def _int4_pack(q4: Array) -> Array:
    """Two int4 values per uint8 (even positions low nibble); odd feature
    dims stay int8 — a byte each, still half of bf16."""
    if q4.shape[-1] % 2:
        return q4
    lo = q4[..., 0::2].astype(jnp.int32)
    hi = q4[..., 1::2].astype(jnp.int32)
    return ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.uint8)


def _int4_unpack(q: Array) -> Array:
    b = q.astype(jnp.int32)
    lo = ((b & 0xF) ^ 8) - 8            # sign-extend the nibble
    hi = ((b >> 4) ^ 8) - 8
    return jnp.stack([lo, hi], axis=-1).reshape(
        *q.shape[:-1], q.shape[-1] * 2).astype(jnp.int8)


def _q8_encode(x: Array) -> Tuple[Array, Array]:
    return wire_encode(x, "int8")


def _q8_decode(q: Array, scale: Array, dtype) -> Array:
    return wire_decode((q, scale), "int8", dtype)


def _wire_hop(acc: Array, axis: str, perm, wire_dtype: Optional[str]) -> Array:
    """One ppermute ring hop, optionally quantized on the wire (encode ->
    hop the payload pair -> decode; lossy per hop by design)."""
    if not wire_dtype:
        return lax.ppermute(acc, axis, perm)
    # nested "wire" scope: the census identifies quantized transports by
    # it (a quantized AR ring legitimately ppermutes under the replicated
    # layout — psum cannot carry the per-block scales)
    with _seam_scope("wire"):
        payloads = wire_encode(acc, wire_dtype)
        payloads = tuple(lax.ppermute(p, axis, perm) for p in payloads)
        return wire_decode(payloads, wire_dtype, acc.dtype)


def _gather_full(x: Array, axis: str, wire_dtype: Optional[str]) -> Array:
    """Monolithic (xla-mode) sequence gather, optionally wire-quantized."""
    with _seam_scope("ag_full"):
        if not wire_dtype:
            return lax.all_gather(x, axis, axis=x.ndim - 2, tiled=True)
        q, sc = wire_encode(x, wire_dtype)
        qf = lax.all_gather(q, axis, axis=q.ndim - 2, tiled=True)
        sf = lax.all_gather(sc, axis, axis=sc.ndim - 2, tiled=True)
        return wire_decode((qf, sf), wire_dtype, x.dtype)


# ---------------------------------------------------------------------------
# GEMM-ReduceScatter transports (single ring pass even for multiple pairs)
# ---------------------------------------------------------------------------
def _rs_partial(ys: Tuple[Array, ...], ws: Tuple[Array, ...], owner,
                s_shard: int, length: Optional[int] = None,
                offset: int = 0):
    """sum_i ys_i[owner's seq rows] @ ws_i — the per-owner partial of the
    multi-pair reduce-scatter (one ring carries the SUMMED partial)."""
    length = s_shard if length is None else length
    acc = None
    for y, w in zip(ys, ws):
        ysl = lax.dynamic_slice_in_dim(y, owner * s_shard + offset, length,
                                       axis=y.ndim - 2)
        p = jnp.einsum("...sf,fd->...sd", ysl, w)
        acc = p if acc is None else acc + p
    return acc


def _rs_ring(ys: Tuple[Array, ...], ws: Tuple[Array, ...], axis: str,
             comm_chunks: int, reverse: bool,
             wire_dtype: Optional[str] = None) -> Array:
    """GEMM-ReduceScatter ring: at step s each device computes ONLY the
    output chunk the ring needs next, adds the partial arriving from its
    neighbor, and forwards (paper Fig. 3, medium-grained).  ``wire_dtype``
    quantizes the travelling ACCUMULATOR before each hop (requantized per
    hop — the sum itself stays float)."""
    n = compat.axis_size(axis)
    me = lax.axis_index(axis)
    seq = ys[0].shape[-2]
    assert seq % n == 0, f"seq {seq} not divisible by TP {n}"
    s_shard = seq // n

    def owner_at(s):
        return ((me - (n - 1 - s)) % n if reverse
                else (me + n - 1 - s) % n)

    with _seam_scope("rs_ring"):
        acc = _rs_partial(ys, ws, owner_at(0), s_shard)
        for s in range(1, n):
            acc = _wire_hop(acc, axis, _ring_perm(axis, reverse), wire_dtype)
            acc = acc + _rs_partial(ys, ws, owner_at(s), s_shard)
    return acc


def _rs_bidir(ys: Tuple[Array, ...], ws: Tuple[Array, ...], axis: str,
              comm_chunks: int, wire_dtype: Optional[str] = None) -> Array:
    n = compat.axis_size(axis)
    me = lax.axis_index(axis)
    seq = ys[0].shape[-2]
    s_shard = seq // n
    if s_shard % 2:
        return _rs_ring(ys, ws, axis, comm_chunks, False, wire_dtype)
    half = s_shard // 2

    def partial(owner, top: bool):
        return _rs_partial(ys, ws, owner, s_shard, half,
                           0 if top else half)

    # top halves accumulate rightward, bottom halves leftward
    with _seam_scope("rs_bidir"):
        acc_r = partial((me + n - 1) % n, True)
        acc_l = partial((me - (n - 1)) % n, False)
        for s_ in range(1, n):
            acc_r = _wire_hop(acc_r, axis, _ring_perm(axis), wire_dtype)
            acc_l = _wire_hop(acc_l, axis, _ring_perm(axis, reverse=True),
                              wire_dtype)
            acc_r = acc_r + partial((me + n - 1 - s_) % n, True)
            acc_l = acc_l + partial((me - (n - 1) + s_) % n, False)
    return jnp.concatenate([acc_r, acc_l], axis=acc_r.ndim - 2)


def _rs_core(ys: Tuple[Array, ...], ws: Tuple[Array, ...], axis, mode: str,
             comm_chunks: int, reverse: bool, blocks,
             wire_dtype: Optional[str] = None) -> Array:
    """sum_i ReduceScatter_seq(ys_i @ ws_i) with ONE collective pass.

    ``wire_dtype`` quantizes the ring modes' travelling partials;
    ``xla``'s monolithic ``psum_scatter`` cannot carry mixed-scale
    payloads, so it ignores the knob (documented baseline)."""
    mode, wire_dtype = normalize_mode(mode, wire_dtype)
    if axis is None or _axis_size(axis) == 1:
        acc = None
        for y, w in zip(ys, ws):
            p = jnp.einsum("...sf,fd->...sd", y, w)
            acc = p if acc is None else acc + p
        return acc
    if mode == "xla":
        acc = None
        for y, w in zip(ys, ws):
            p = jnp.einsum("...sf,fd->...sd", y, w)
            acc = p if acc is None else acc + p
        with _seam_scope("rs_scatter"):
            return lax.psum_scatter(acc, axis,
                                    scatter_dimension=acc.ndim - 2,
                                    tiled=True)
    if mode == "flux":
        # multi-pair RS == single RS of the concatenated operands (the
        # contraction dim stacks): still one fused kernel / one ring pass.
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=-1)
        w = ws[0] if len(ws) == 1 else jnp.concatenate(ws, axis=0)
        return _rs_flux(y, w, axis, reverse, blocks)
    if mode == "decomposed_bidir":
        return _rs_bidir(ys, ws, axis, comm_chunks, wire_dtype)
    return _rs_ring(ys, ws, axis, comm_chunks, reverse, wire_dtype)


def _ar_ring_quant(p: Array, axis: str, wire_dtype: str) -> Array:
    """Ring all-reduce of a per-rank FULL partial with quantized hops
    (Flash-Communication style): ring reduce-scatter over last-dim shards
    (the travelling accumulator is requantized per hop; each rank's OWN
    partial joins in full precision), then a ring all-gather of the
    reduced shards (quantized once each; the locally-reduced shard stays
    float).  ``lax.psum`` cannot carry mixed-scale payloads, which is why
    the quantized all-reduce is spelled as these two rings."""
    n = compat.axis_size(axis)
    me = lax.axis_index(axis)
    d = p.shape[-1]
    shard = d // n

    def owner_at(s):
        return (me + n - 1 - s) % n

    def part(s):
        return lax.dynamic_slice_in_dim(p, owner_at(s) * shard, shard,
                                        axis=p.ndim - 1)

    acc = part(0)
    for s in range(1, n):
        acc = _wire_hop(acc, axis, _ring_perm(axis), wire_dtype)
        acc = acc + part(s)
    # acc = the fully-reduced shard this rank owns; gather the rest
    out = jnp.zeros_like(p)
    out = lax.dynamic_update_slice_in_dim(out, acc.astype(p.dtype),
                                          me * shard, axis=p.ndim - 1)
    with _seam_scope("wire"):
        payloads = wire_encode(acc, wire_dtype)
        for step in range(1, n):
            payloads = tuple(lax.ppermute(pl, axis, _ring_perm(axis))
                             for pl in payloads)
            owner = (me - step) % n
            chunk = wire_decode(payloads, wire_dtype, p.dtype)
            out = lax.dynamic_update_slice_in_dim(out, chunk, owner * shard,
                                                  axis=p.ndim - 1)
    return out


def _ar_core(y: Array, w: Array, axis, mode: str, comm_chunks: int,
             wire_dtype: Optional[str] = None) -> Array:
    """AllReduce(y @ w) — the decode-path row-parallel GEMM, chunked along
    the contraction dim so each partial psum overlaps with the next chunk's
    GEMM (``decomposed*``); xla/flux use one monolithic psum (one-token
    GEMMs are latency- not bandwidth-bound).  ``wire_dtype`` under the
    decomposed modes rides the quantized two-ring all-reduce
    (``_ar_ring_quant``); psum-based paths ignore it."""
    mode, wire_dtype = normalize_mode(mode, wire_dtype)
    if axis is None or _axis_size(axis) == 1:
        return jnp.einsum("...mf,fd->...md", y, w)
    if mode.startswith("decomposed"):
        n = compat.axis_size(axis)
        if wire_dtype and w.shape[-1] % n == 0:
            with _seam_scope("ar"):
                return _ar_ring_quant(jnp.einsum("...mf,fd->...md", y, w),
                                      axis, wire_dtype)
        k = y.shape[-1]
        chunks = comm_chunks if comm_chunks else n
        chunks = max(1, min(chunks, k))
        while k % chunks:
            chunks -= 1
        ck = k // chunks
        parts = []
        with _seam_scope("ar"):
            for c in range(chunks):
                yc = lax.dynamic_slice_in_dim(y, c * ck, ck, axis=y.ndim - 1)
                wc = lax.dynamic_slice_in_dim(w, c * ck, ck, axis=0)
                parts.append(lax.psum(jnp.einsum("...mf,fd->...md", yc, wc),
                                      axis))
        out = parts[0]
        for p in parts[1:]:
            out = out + p
        return out
    with _seam_scope("ar"):
        return lax.psum(jnp.einsum("...mf,fd->...md", y, w), axis)


# ---------------------------------------------------------------------------
# kind="a2a": the MoE expert-parallel token exchange (dispatch + combine)
# ---------------------------------------------------------------------------
def _ep_group_size(axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= compat.axis_size(a)
    return n


def a2a_exchange(buf: Array, axes: Sequence[str]) -> Array:
    """Barrier all-to-all of ``buf[EP, ...]`` over an EP group spanning one
    or more mesh axes (rank order axis-major, matching the router's
    ``ep_rank = ep_rank * size(a) + axis_index(a)`` computation).  The
    exchange is an involution — and its own transpose — so the same call
    serves dispatch, combine, and both backward directions.  Callers wrap
    it in a ``seam_*`` scope (census provenance)."""
    if len(axes) == 1:
        return lax.all_to_all(buf, axes[0], split_axis=0, concat_axis=0,
                              tiled=True)
    sizes = [compat.axis_size(a) for a in axes]
    shaped = buf.reshape(*sizes, *buf.shape[1:])
    for i, a in enumerate(axes):
        shaped = lax.all_to_all(shaped, a, split_axis=i, concat_axis=i,
                                tiled=True)
    return shaped.reshape(buf.shape)


def _expert_fn(epi: Epilogue, b: Array, w1: Array, w3: Array,
               w2: Array) -> Array:
    """Per-local-expert gated FFN on one (sub-)chunk of the received
    dispatch buffer: b[..., e_loc, c, dm] @ (w1, w3)[e_loc, dm, df] ->
    pair-gate -> @ w2[e_loc, df, dm]."""
    a1 = jnp.einsum("...ecd,edf->...ecf", b, w1)
    a3 = jnp.einsum("...ecd,edf->...ecf", b, w3)
    h = epi.apply([a1, a3])
    return jnp.einsum("...ecf,efd->...ecd", h, w2)


def _ep_shifts(op: FusedOp, axes, sizes):
    """Per-axis shift vectors enumerating every EP partner exactly once
    (mixed-radix digits of the step index; ``reverse`` flips the ring
    direction).  For each shift vector the send map ``idx -> idx + sh``
    (per-axis modular) is a bijection realized by one ppermute per
    involved axis."""
    strides = []
    for k in range(len(sizes)):
        st = 1
        for nj in sizes[k + 1:]:
            st *= nj
        strides.append(st)
    ep = _ep_group_size(axes)
    out = []
    for s in range(ep):
        shs = [(s // st) % nk for st, nk in zip(strides, sizes)]
        if op.reverse:
            shs = [(nk - sh) % nk for sh, nk in zip(shs, sizes)]
        out.append(shs)
    return out, strides


def _ep_flat(idx, shs, sizes, strides, sign: int):
    """Axis-major flat EP rank of (idx +/- shs) per-axis modular."""
    flat = 0
    for ix, sh, nk, st in zip(idx, shs, sizes, strides):
        flat = flat + ((ix + sign * sh) % nk) * st
    return flat


def _a2a_ring(op: FusedOp, x, ws, epi: Epilogue):
    """Over-decomposed EP exchange: per (shift, sub-chunk) stage, the chunk
    destined for the shifted partner hops forward on ppermutes, the local
    experts' gated GEMMs consume what arrived, and the result hops back on
    the inverse ppermutes — chunk i's GEMM is dataflow-independent of chunk
    i+1's hops, so the scheduler overlaps them (paper §4.3, applied to the
    dispatch AND combine directions at once).  Returns ``(out, buf)`` with
    ``buf[i] = x_i[me]`` (the assembled received buffer, the backward's
    saved residual) identical to the barrier path's."""
    axes = op.axis
    sizes = [compat.axis_size(a) for a in axes]
    idx = [lax.axis_index(a) for a in axes]
    shifts, strides = _ep_shifts(op, axes, sizes)
    ep = len(shifts)
    e_loc, cap, dm = x.shape[1:]
    sub = _sub_chunks(cap, ep, op.comm_chunks)
    sub_len = cap // sub

    out = jnp.zeros_like(x)
    buf = jnp.zeros_like(x)
    with _seam_scope("moe_a2a_ring"):
        for shs in shifts:
            dst = _ep_flat(idx, shs, sizes, strides, +1)
            src = _ep_flat(idx, shs, sizes, strides, -1)
            fwd = [(a, [(i, (i + sh) % nk) for i in range(nk)])
                   for a, sh, nk in zip(axes, shs, sizes) if sh]
            inv = [(a, [(i, (i - sh) % nk) for i in range(nk)])
                   for a, sh, nk in zip(axes, shs, sizes) if sh]
            for j in range(sub):
                off = j * sub_len
                chunk = lax.dynamic_slice(x, (dst, 0, off, 0),
                                          (1, e_loc, sub_len, dm))
                if op.wire_dtype:
                    # dispatch tokens quantized on the wire; the expert
                    # GEMM (and the saved buffer) see the decoded chunk.
                    # The combine direction stays full precision — the
                    # expert outputs feed the router-weighted sum.
                    payloads = wire_encode(chunk, op.wire_dtype)
                    for a, perm in fwd:
                        payloads = tuple(lax.ppermute(p, a, perm)
                                         for p in payloads)
                    chunk = wire_decode(payloads, op.wire_dtype, x.dtype)
                else:
                    for a, perm in fwd:
                        chunk = lax.ppermute(chunk, a, perm)
                # arrived = x_src[me]: the partner's tokens for MY experts
                buf = lax.dynamic_update_slice(buf, chunk, (src, 0, off, 0))
                y = _expert_fn(epi, chunk, *ws)
                for a, perm in reversed(inv):
                    y = lax.ppermute(y, a, perm)
                # received = E_dst(x_me[dst]): my tokens, expert-processed
                out = lax.dynamic_update_slice(out, y.astype(out.dtype),
                                               (dst, 0, off, 0))
    return out, buf


def _a2a_impl(op: FusedOp, x, ws):
    """(out, received_buf) of the EP exchange.  ``xla*`` modes run the two
    barrier all_to_alls around the batched expert GEMMs; every other mode
    rides the interleaved ppermute pipeline."""
    epi = op.epilogue
    axes = op.axis
    if not axes or _ep_group_size(axes) == 1:
        return _expert_fn(epi, x, *ws), x
    if op.mode == "xla":
        with _seam_scope("moe_a2a_dispatch"):
            if op.wire_dtype:
                q, sc = wire_encode(x, op.wire_dtype)
                qf = a2a_exchange(q, axes)
                sf = a2a_exchange(sc, axes)
                buf = wire_decode((qf, sf), op.wire_dtype, x.dtype)
            else:
                buf = a2a_exchange(x, axes)
        y = _expert_fn(epi, buf, *ws)
        with _seam_scope("moe_a2a_combine"):
            out = a2a_exchange(y, axes)
        return out.astype(x.dtype), buf
    return _a2a_ring(op, x, ws, epi)


def _a2a_bwd_ring(op: FusedOp, x, ws, buf, g, epi: Epilogue):
    """Backward rides the interchanged op: the combine cotangent chunk hops
    along the DISPATCH perms (pairing it with the saved received buffer for
    the per-chunk expert vjp), and the input cotangent returns on the
    inverse hops.  dW accumulates locally — each rank's experts are
    rank-exclusive, so the sum over arriving chunks IS the full gradient
    (no completing psum; seamcheck expects none)."""
    axes = op.axis
    sizes = [compat.axis_size(a) for a in axes]
    idx = [lax.axis_index(a) for a in axes]
    shifts, strides = _ep_shifts(op, axes, sizes)
    ep = len(shifts)
    e_loc, cap, dm = x.shape[1:]
    sub = _sub_chunks(cap, ep, op.comm_chunks)
    sub_len = cap // sub

    dx = jnp.zeros_like(x)
    dws = None
    with _seam_scope("moe_a2a_ring"):
        for shs in shifts:
            dst = _ep_flat(idx, shs, sizes, strides, +1)
            src = _ep_flat(idx, shs, sizes, strides, -1)
            fwd = [(a, [(i, (i + sh) % nk) for i in range(nk)])
                   for a, sh, nk in zip(axes, shs, sizes) if sh]
            inv = [(a, [(i, (i - sh) % nk) for i in range(nk)])
                   for a, sh, nk in zip(axes, shs, sizes) if sh]
            for j in range(sub):
                off = j * sub_len
                gc = lax.dynamic_slice(g, (dst, 0, off, 0),
                                       (1, e_loc, sub_len, dm))
                for a, perm in fwd:
                    gc = lax.ppermute(gc, a, perm)
                # gc = g_src[me]: cotangent of MY experts' output on the
                # chunk received from src — pair with the saved input
                if op.wire_dtype:
                    # forward-wire-only quantization: the saved buf is
                    # lossy, so rebuild the FULL-precision received chunk
                    # by re-running the fp dispatch hops (ppermute/slice
                    # are exact — grads bit-match the fp wire's)
                    bc = lax.dynamic_slice(x, (dst, 0, off, 0),
                                           (1, e_loc, sub_len, dm))
                    for a, perm in fwd:
                        bc = lax.ppermute(bc, a, perm)
                else:
                    bc = lax.dynamic_slice(buf, (src, 0, off, 0),
                                           (1, e_loc, sub_len, dm))
                _, vjp = jax.vjp(functools.partial(_expert_fn, epi),
                                 bc, *ws)
                db, *dw = vjp(gc.astype(bc.dtype))
                dws = dw if dws is None else [a_ + b_ for a_, b_
                                              in zip(dws, dw)]
                for a, perm in reversed(inv):
                    db = lax.ppermute(db, a, perm)
                dx = lax.dynamic_update_slice(dx, db.astype(dx.dtype),
                                              (dst, 0, off, 0))
    return dx, tuple(d.astype(w.dtype) for d, w in zip(dws, ws))


def _a2a_bwd(op: FusedOp, res, g):
    x, ws, buf, _, _, _ = res
    epi = op.epilogue
    axes = op.axis

    def local_vjp(b, ct):
        _, vjp = jax.vjp(functools.partial(_expert_fn, epi), b, *ws)
        db, *dw = vjp(ct.astype(b.dtype))
        return db, tuple(d.astype(w.dtype) for d, w in zip(dw, ws))

    if not axes or _ep_group_size(axes) == 1:
        dx, dws = local_vjp(x, g)
    elif op.mode == "xla":
        if op.wire_dtype:
            # the saved buf is wire-lossy; rebuild the fp received buffer
            # (exact exchange) so the backward matches the fp wire's
            with _seam_scope("moe_a2a_dispatch"):
                buf = a2a_exchange(x, axes)
        with _seam_scope("moe_a2a_combine"):
            gb = a2a_exchange(g, axes)      # combine's transpose
        db, dws = local_vjp(buf, gb)
        with _seam_scope("moe_a2a_dispatch"):
            dx = a2a_exchange(db, axes)     # dispatch's transpose
    else:
        dx, dws = _a2a_bwd_ring(op, x, ws, buf, g, epi)
    return dx.astype(x.dtype), dws, None, None, None


# ---------------------------------------------------------------------------
# mode="flux": fused Pallas kernels (see repro/kernels/)
# ---------------------------------------------------------------------------
def _blocks_kw(blocks) -> dict:
    if blocks is None:
        return {}
    bm, bk, bn = blocks
    return {"bm": bm, "bk": bk, "bn": bn}


def _ag_flux(x: Array, w: Array, axis: str, reverse: bool, blocks,
             activation: Optional[str] = None,
             bias: Optional[Array] = None) -> Array:
    from repro.kernels import ops as kops
    # Kernels operate on [m_shard, k] @ [k, n] 2-D operands and gather along
    # m in SHARD-MAJOR order.  Move the (sharded) sequence dim to the front so
    # shard-major == sequence order, then flatten the batch dims into m.
    n = _axis_size(axis)
    lead = x.shape[:-2]
    xt = jnp.moveaxis(x, -2, 0)                        # [S/N, *lead, D]
    x2 = xt.reshape((-1, x.shape[-1]))                 # [(S/N)*B_flat, D]
    y2 = kops.ag_matmul_fused(x2, w, axis_name=axis, reverse=reverse,
                              activation=activation, bias=bias,
                              **_blocks_kw(blocks))    # [S*B_flat, F/N]
    yt = y2.reshape((x.shape[-2] * n, *lead, w.shape[-1]))
    return jnp.moveaxis(yt, 0, -2)                     # [*lead, S, F/N]


def _rs_flux(y: Array, w: Array, axis: str, reverse: bool, blocks,
             activation: Optional[str] = None,
             bias: Optional[Array] = None) -> Array:
    from repro.kernels import ops as kops
    n = _axis_size(axis)
    lead = y.shape[:-2]
    yt = jnp.moveaxis(y, -2, 0)                        # [S, *lead, F/N]
    y2 = yt.reshape((-1, y.shape[-1]))
    o2 = kops.matmul_rs_fused(y2, w, axis_name=axis, reverse=reverse,
                              activation=activation, bias=bias,
                              **_blocks_kw(blocks))    # [S/N * B_flat, D]
    ot = o2.reshape((y.shape[-2] // n, *lead, w.shape[-1]))
    return jnp.moveaxis(ot, 0, -2)                     # [*lead, S/N, D]


# ---------------------------------------------------------------------------
# FusedOp: the declarative op object
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FusedOp:
    """One TP-seam collective-matmul with a fused epilogue (module docstring
    for semantics).  Frozen + hashable: the op itself is the custom_vjp's
    static configuration, so equal plans share one trace."""
    kind: str
    axis: Optional[str] = None
    mode: str = "decomposed"
    comm_chunks: int = 0
    reverse: bool = False
    blocks: Optional[Tuple[int, int, int]] = None
    epilogue: Epilogue = Epilogue()
    n_weights: int = 1
    fuse_epilogue: bool = True
    shared_gather: bool = True
    scatter_axis: str = "seq"
    wire_dtype: Optional[str] = None

    def __post_init__(self):
        mode, wd = normalize_mode(self.mode, self.wire_dtype)
        if (mode, wd) != (self.mode, self.wire_dtype):
            object.__setattr__(self, "mode", mode)
            object.__setattr__(self, "wire_dtype", wd)
        if self.kind not in VALID_KINDS:
            raise ValueError(f"invalid kind {self.kind!r}")
        if self.mode not in VALID_MODES:
            raise ValueError(f"invalid overlap mode {self.mode!r}")
        if self.wire_dtype not in VALID_WIRE_DTYPES:
            raise ValueError(f"invalid wire_dtype {self.wire_dtype!r}")
        if self.wire_dtype is not None and self.mode == "flux":
            raise ValueError(
                "wire_dtype is not supported with mode='flux' (the Pallas "
                "kernels have no quantized DMA path); use a decomposed "
                "mode or drop wire_dtype")
        if self.scatter_axis not in VALID_SCATTER_AXES:
            raise ValueError(f"invalid scatter_axis {self.scatter_axis!r}")
        if self.kind == "ar":
            # "ar" IS the replicated layout (one-token decode GEMMs)
            object.__setattr__(self, "scatter_axis", "hidden")
        if self.n_weights < 1:
            raise ValueError("n_weights must be >= 1")
        if self.kind == "a2a":
            # EP exchange: axis is a TUPLE of mesh axes (rank order is
            # axis-major); the op owns the whole expert computation, so it
            # takes the (w1, w3, w2) triple and the pure pair-gate epilogue.
            axes = self.axis
            if axes is None:
                axes = ()
            elif isinstance(axes, str):
                axes = (axes,)
            object.__setattr__(self, "axis", tuple(axes))
            if self.n_weights != 3:
                raise ValueError(
                    'kind="a2a" takes the expert (w1, w3, w2) triple')
            e = self.epilogue
            if e.gate != "pair" or e.bias or e.scale or e.residual:
                raise ValueError(
                    'kind="a2a" needs a pure gate="pair" epilogue')
            if self.blocks is not None:
                object.__setattr__(self, "blocks", tuple(self.blocks))
            return
        if self.kind != "ag" and self.n_weights != 1:
            raise ValueError(f"kind={self.kind!r} ops take exactly one weight")
        if self.epilogue.gate == "pair":
            if self.kind != "ag" or self.n_weights != 2:
                raise ValueError('gate="pair" needs an ag op with n_weights=2')
        elif self.n_weights > 1 and not self.epilogue.is_identity:
            raise ValueError("multi-output ops (n_weights>1 without "
                             'gate="pair") require an identity epilogue')
        if self.blocks is not None:
            object.__setattr__(self, "blocks", tuple(self.blocks))

    @staticmethod
    def from_plan(kind: str, plan, axis: Optional[str] = None,
                  epilogue: Optional[Epilogue] = None,
                  n_weights: int = 1,
                  scatter_axis: Optional[str] = None) -> "FusedOp":
        """Bind a tuning ``SeamPlan`` (duck-typed: anything with
        mode/comm_chunks/...) to a concrete seam op.  ``scatter_axis=None``
        takes the plan's layout knob (the context layer passes the model's
        resolved residual layout explicitly, keeping all seams coherent)."""
        blocks = getattr(plan, "blocks", None)
        return FusedOp(
            kind=kind, axis=axis, mode=plan.mode,
            comm_chunks=plan.comm_chunks,
            reverse=getattr(plan, "reverse", False),
            blocks=tuple(blocks) if blocks else None,
            epilogue=epilogue if epilogue is not None else Epilogue(),
            n_weights=n_weights,
            fuse_epilogue=getattr(plan, "fuse_epilogue", True),
            shared_gather=getattr(plan, "shared_gather", True),
            scatter_axis=(scatter_axis if scatter_axis is not None
                          else getattr(plan, "scatter_axis", "seq")),
            wire_dtype=getattr(plan, "wire_dtype", None))

    @property
    def combines(self) -> bool:
        """True when the op returns ONE array (single weight or pair-gate);
        False -> tuple of per-weight outputs."""
        return self.n_weights == 1 or self.epilogue.gate == "pair"

    def __call__(self, x: Array, *ws: Array, bias=None, scale=None,
                 residual=None):
        if len(ws) != self.n_weights:
            raise ValueError(f"expected {self.n_weights} weights, "
                             f"got {len(ws)}")
        epi = self.epilogue
        for flag, name, val in ((epi.bias, "bias", bias),
                                (epi.scale, "scale", scale),
                                (epi.residual, "residual", residual)):
            if flag != (val is not None):
                raise ValueError(
                    f"epilogue.{name}={flag} but {name} operand "
                    f"{'missing' if flag else 'given'}")
        return _fused(self, x, tuple(ws), bias, scale, residual)


def _apply_epilogue(op: FusedOp, ys: Sequence[Array], bias, scale, residual):
    """Epilogue at the op level: combine to one array, or pass the
    per-weight outputs through as a tuple (identity epilogue)."""
    if op.combines:
        return op.epilogue.apply(ys, bias=bias, scale=scale,
                                 residual=residual)
    return tuple(ys)


# ---------------------------------------------------------------------------
# forward implementations
# ---------------------------------------------------------------------------
def _fused_ag(op: FusedOp, x, ws, bias, scale, residual):
    epi = op.epilogue
    mode = op.mode
    if (op.axis is None or _axis_size(op.axis) == 1
            or op.scatter_axis == "hidden"):
        # hidden layout: x is already the FULL replicated activation — the
        # column-parallel GEMM needs no collective at all (Megatron's "f").
        ys = [jnp.einsum("...sd,df->...sf", x, w) for w in ws]
        return _apply_epilogue(op, ys, bias, scale, residual)

    if mode == "flux":
        return _fused_ag_flux(op, x, ws, bias, scale, residual)

    if mode == "xla":
        full = _gather_full(x, op.axis, op.wire_dtype)
        ys = [jnp.einsum("...sd,df->...sf", full, w) for w in ws]
        return _apply_epilogue(op, ys, bias, scale, residual)

    # ring transports: the epilogue fuses PER CHUNK inside the overlapped
    # loop (residual is row-indexed by global position -> applied after
    # assembly; everything else is chunk-local).
    per_chunk = (op.fuse_epilogue and op.combines and not epi.is_identity
                 and (op.shared_gather or op.n_weights == 1))
    epi_chunk = dataclasses.replace(epi, residual=False)

    def chunk_fn(xc):
        ys = [jnp.einsum("...sd,df->...sf", xc, w) for w in ws]
        if per_chunk:
            return (epi_chunk.apply(ys, bias=bias, scale=scale),)
        return tuple(ys)

    wd = op.wire_dtype
    enc = (lambda v: wire_encode(v, wd)) if wd else None
    dec = (lambda buf: wire_decode(buf, wd, x.dtype)) if wd else None

    def run(fn):
        if mode == "decomposed_bidir":
            return _ag_bidir(x, op.axis, op.comm_chunks, fn,
                             encode=enc, decode=dec)
        return _ag_ring(x, op.axis, op.comm_chunks, op.reverse, fn,
                        encode=enc, decode=dec)

    if op.shared_gather or op.n_weights == 1:
        outs = run(chunk_fn)          # ONE ring pass for all weights
    else:
        outs = tuple(run(lambda xc, w=w: (jnp.einsum("...sd,df->...sf",
                                                     xc, w),))[0]
                     for w in ws)     # legacy: one ring per weight

    if per_chunk:
        out = outs[0]
        if epi.residual:
            out = out + residual
        return out
    return _apply_epilogue(op, list(outs), bias, scale, residual)


def _fused_ag_flux(op: FusedOp, x, ws, bias, scale, residual):
    epi = op.epilogue
    # single-weight bias/activation fuse into the kernel's tile epilogue
    if (op.n_weights == 1 and op.fuse_epilogue and not epi.scale
            and epi.gate is None):
        y = _ag_flux(x, ws[0], op.axis, op.reverse, op.blocks,
                     activation=epi.activation,
                     bias=bias if epi.bias else None)
        if epi.residual:
            y = y + residual
        return y
    if op.n_weights > 1 and op.shared_gather:
        # shared gather via one kernel over the column-stacked weights:
        # gather once, one ring of DMA hops, split the local outputs.
        wcat = jnp.concatenate(ws, axis=-1)
        ycat = _ag_flux(x, wcat, op.axis, op.reverse, op.blocks)
        offs, splits = 0, []
        for w in ws[:-1]:
            offs += w.shape[-1]
            splits.append(offs)
        ys = jnp.split(ycat, splits, axis=-1)
    else:
        ys = [_ag_flux(x, w, op.axis, op.reverse, op.blocks) for w in ws]
    return _apply_epilogue(op, ys, bias, scale, residual)


def _fused_z(op: FusedOp, x, ws):
    """Pre-epilogue output of an rs/ar op (the collective's result)."""
    if op.kind == "rs" and op.scatter_axis == "seq":
        return _rs_core((x,), ws, op.axis, op.mode, op.comm_chunks,
                        op.reverse, op.blocks, op.wire_dtype)
    # rs/hidden degenerates to the row-parallel GEMM + AllReduce
    # (Megatron's "g" without the sequence scatter) — exactly the "ar" op.
    return _ar_core(x, ws[0], op.axis, op.mode, op.comm_chunks,
                    op.wire_dtype)


def _fused_impl(op: FusedOp, x, ws, bias, scale, residual):
    if op.kind == "ag":
        return _fused_ag(op, x, ws, bias, scale, residual)
    if op.kind == "a2a":
        return _a2a_impl(op, x, ws)[0]
    z = _fused_z(op, x, ws)
    return op.epilogue.apply([z], bias=bias, scale=scale, residual=residual)


# ---------------------------------------------------------------------------
# custom_vjp — ONCE, at the FusedOp level
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused(op: FusedOp, x, ws, bias, scale, residual):
    return _fused_impl(op, x, ws, bias, scale, residual)


def _fused_fwd(op: FusedOp, x, ws, bias, scale, residual):
    if op.kind == "ag":
        # pre-epilogue activations are RE-DERIVED in bwd from the dW
        # re-gather (one all_gather serves the epilogue-vjp AND every dW)
        out = _fused_ag(op, x, ws, bias, scale, residual)
        return out, (x, ws, None, bias, scale, residual)
    if op.kind == "a2a":
        # the RECEIVED dispatch buffer rides the z residual slot: backward
        # pairs it with the returning combine cotangent per chunk
        out, buf = _a2a_impl(op, x, ws)
        return out, (x, ws, buf, bias, scale, residual)
    z = _fused_z(op, x, ws)
    out = op.epilogue.apply([z], bias=bias, scale=scale, residual=residual)
    return out, (x, ws, z, bias, scale, residual)


def _fused_bwd(op: FusedOp, res, g):
    if op.kind == "a2a":
        # rides the interchanged exchange (axis is a TUPLE here — before
        # the scalar-axis handling below)
        return _a2a_bwd(op, res, g)
    x, ws, z, bias, scale, residual = res
    epi = op.epilogue
    single = op.axis is None or _axis_size(op.axis) == 1

    hidden = op.scatter_axis == "hidden"
    if op.kind == "ag":
        # the dW contraction needs the gathered activation anyway (a
        # "sequence-partial + psum" variant was tried and REFUTED: each
        # device's g covers different weight columns, so shard-partials
        # cannot be psum-combined; see EXPERIMENTS.md §Perf iteration log).
        # hidden layout: x is already full — no re-gather at all.  seq
        # layout: the re-gather rides the op's own transport (gather_seq:
        # ppermute ring for the ring modes) so no standalone all_gather
        # remains in the step.
        xf = x if (single or hidden) else gather_seq(x, op.axis, op.mode,
                                                     op.reverse)
        ys = tuple(jnp.einsum("...sd,df->...sf", xf, w) for w in ws)

        def epi_fn(ys_, bias_, scale_, residual_):
            if op.combines:
                return epi.apply(ys_, bias=bias_, scale=scale_,
                                 residual=residual_)
            return tuple(ys_)

        _, epi_vjp = jax.vjp(epi_fn, ys, bias, scale, residual)
        dys, dbias, dscale, dres = epi_vjp(g)
        # dX: the interchanged op.  seq — GEMM + ReduceScatter over the
        # sequence cotangent, ONE ring pass for all weights (blocks are
        # tuned for the forward shape; the transposed op auto-plans its
        # own).  hidden — NO collective: under check_rep=False shard_map,
        # a replicated tensor's cotangent is a per-rank PARTIAL that sums
        # to the truth across ranks, and the local sum over this rank's
        # weight columns IS that partial.  (The completing psum happens at
        # whichever op consumes the replicated stream with a rank-exclusive
        # operand — see the rs/ar branch below.)
        wts = tuple(w.T for w in ws)
        if single or hidden:
            dx = None
            for dy, wt in zip(dys, wts):
                p = jnp.einsum("...sf,fd->...sd", dy, wt)
                dx = p if dx is None else dx + p
        else:
            # cotangents never ride a quantized wire (wire_dtype=None)
            dx = _rs_core(dys, wts, op.axis, op.mode, op.comm_chunks,
                          op.reverse, None, None)
        dws = tuple(jnp.einsum("...sd,...sf->df", xf, dy).astype(w.dtype)
                    for w, dy in zip(ws, dys))
        return dx.astype(x.dtype), dws, dbias, dscale, dres

    # rs / ar: epilogue vjp at the saved pre-epilogue output, then the
    # interchanged overlapped op on the transposed cotangent.
    def epi_fn(z_, bias_, scale_, residual_):
        return epi.apply([z_], bias=bias_, scale=scale_, residual=residual_)

    _, epi_vjp = jax.vjp(epi_fn, z, bias, scale, residual)
    dz, dbias, dscale, dres = epi_vjp(g)
    w = ws[0]
    if op.kind == "rs" and not hidden:
        # dY: AllGather + GEMM — interchanged overlapped op.  dz is the
        # cotangent of rank-EXCLUSIVE sequence rows, so it arrives full.
        bwd_op = dataclasses.replace(op, kind="ag", epilogue=Epilogue(),
                                     blocks=None, wire_dtype=None)
        dy = _fused_ag(bwd_op, dz, (w.T,), None, None, None)
        gf = dz if single else gather_seq(dz, op.axis, op.mode, op.reverse)
        dw = jnp.einsum("...sf,...sd->fd", x, gf)
    else:
        # rs/hidden and ar: z is REPLICATED, so its cotangent arrives as a
        # per-rank partial (check_rep=False convention).  This op's x and w
        # are rank-exclusive (hidden/contraction shards), so complete the
        # cotangent with the interchanged collective (psum — the AllReduce
        # backward of the AllReduce forward) BEFORE the local GEMMs.
        if single:
            dzf = dz
        else:
            with _seam_scope("cotangent_ar"):
                dzf = lax.psum(dz, op.axis)
        dy = jnp.einsum("...md,fd->...mf", dzf, w)
        dw = jnp.einsum("...mf,...md->fd", x, dzf)
    return dy.astype(x.dtype), (dw.astype(w.dtype),), dbias, dscale, dres


_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# Reference (oracle) versions for tests: always the naive collective form.
# ---------------------------------------------------------------------------
def ag_matmul_ref(x: Array, w: Array, axis: Optional[str]) -> Array:
    if axis is None or _axis_size(axis) == 1:
        return jnp.einsum("...sd,df->...sf", x, w)
    full = lax.all_gather(x, axis, axis=x.ndim - 2, tiled=True)
    return jnp.einsum("...sd,df->...sf", full, w)


def matmul_rs_ref(y: Array, w: Array, axis: Optional[str]) -> Array:
    if axis is None or _axis_size(axis) == 1:
        return jnp.einsum("...sf,fd->...sd", y, w)
    partial = jnp.einsum("...sf,fd->...sd", y, w)
    return lax.psum_scatter(partial, axis, scatter_dimension=partial.ndim - 2,
                            tiled=True)
