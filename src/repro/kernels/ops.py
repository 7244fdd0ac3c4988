"""jit-ready wrappers around the Pallas kernels.

Handles block-size planning, interpret-mode selection (CPU container ->
interpret; real TPU -> Mosaic), and padding.

Mosaic slices VMEM/HBM refs only along its (sublane, lane) tiling, so every
wrapper zero-pads its GEMM operands to ``_SUBLANE`` rows (per shard) and
``_LANE`` columns, runs the blocks :func:`plan_blocks` gives for the padded
dims, and slices the padding off the result — exact for a GEMM (zero
rows/columns contribute nothing).  ``plan_blocks`` is the one block chooser:
the planner and the autotuner record its blocks, and the wrappers run them.
At tp=4 ``minicpm_2b`` widths padding turns the FFN's 1440 = 5760/4 columns
into 1536 (an unpadded divisor plan would pick 240-wide blocks, which Mosaic
refuses).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.kernels import ag_gemm as _ag
from repro.kernels import gemm_rs as _rs
from repro.kernels import matmul as _mm

# interpret-mode selection lives in the portability layer (one probe for
# every kernel); kept importable under the old private name.
_interpret_default = compat.interpret_default


_LANE = 128          # last-dim tiling of a TPU ref
_SUBLANE = 16        # second-to-last (bf16 packs two rows per sublane)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def padded_dims(m: int, k: int, n: int):
    """Per-shard GEMM dims [m, k] @ [k, n] as the wrappers run them."""
    return _round_up(m, _SUBLANE), _round_up(k, _LANE), _round_up(n, _LANE)


def pick_block(dim: int, pref: int, align: int) -> int:
    """Largest block <= pref dividing ``dim`` (a multiple of ``align``): a
    multiple of 128 when one divides dim, else a multiple of ``align``."""
    for step in (_LANE, align):
        b = min(pref, dim)
        b -= b % step
        while b >= step:
            if dim % b == 0:
                return b
            b -= step
    return align


def plan_blocks(m: int, k: int, n: int,
                bm: int = 256, bk: int = 512, bn: int = 256):
    """The (bm, bk, bn) the kernels run for a per-shard [m, k] @ [k, n]
    GEMM: each divides its padded dim (:func:`padded_dims`), bm is a
    multiple of ``_SUBLANE`` and bk, bn of ``_LANE``.  Blocks that already
    fit come back unchanged, so a plan's blocks are the blocks that run."""
    pm, pk, pn = padded_dims(m, k, n)
    return (pick_block(pm, bm, _SUBLANE), pick_block(pk, bk, _LANE),
            pick_block(pn, bn, _LANE))


def _pad_dim(x: jax.Array, axis: int, mult: int) -> jax.Array:
    extra = -x.shape[axis] % mult
    if not extra:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def _pad_shard_rows(x: jax.Array, n_dev: int) -> jax.Array:
    """[n_dev*m_sh, c] with owner-major rows -> each owner's m_sh padded to
    a multiple of ``_SUBLANE``."""
    m_sh = x.shape[0] // n_dev
    if m_sh % _SUBLANE == 0:
        return x
    x = _pad_dim(x.reshape(n_dev, m_sh, x.shape[1]), 1, _SUBLANE)
    return x.reshape(-1, x.shape[-1])


def _pad_weight(b: jax.Array, bias: Optional[jax.Array]):
    b = _pad_dim(_pad_dim(b, 0, _LANE), 1, _LANE)
    return b, None if bias is None else _pad_dim(bias, 0, _LANE)


def _blocks(m: int, k: int, n: int, kw: dict):
    return plan_blocks(m, k, n, kw.pop("bm", 256), kw.pop("bk", 512),
                       kw.pop("bn", 256))


def matmul(a: jax.Array, b: jax.Array, *, interpret: Optional[bool] = None,
           **kw) -> jax.Array:
    """Best non-split GEMM (the paper's GEMM_non-split baseline)."""
    interpret = _interpret_default() if interpret is None else interpret
    (m, k), n = a.shape, b.shape[1]
    bm, bk, bn = _blocks(m, k, n, kw)
    a = _pad_dim(_pad_dim(a, 0, _SUBLANE), 1, _LANE)
    b, _ = _pad_weight(b, None)
    out = _mm.matmul(a, b, bm=bm, bk=bk, bn=bn, interpret=interpret, **kw)
    return out[:m, :n]


def _epilogue_by_hand(y: jax.Array, activation: Optional[str],
                      bias: Optional[jax.Array]) -> jax.Array:
    """Single-device fallback for the kernels' fused tile epilogue (same
    fp32 order as the kernels: bias onto the fp32 accumulator, then the
    activation, then the output cast)."""
    from repro.kernels.ag_gemm import EPILOGUE_ACTS
    if activation is None and bias is None:
        return y
    acc = y.astype(jnp.float32)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if activation is not None:
        acc = EPILOGUE_ACTS[activation](acc)
    return acc.astype(y.dtype)


def ag_matmul_fused(a_shard: jax.Array, b_local: jax.Array, *, axis_name: str,
                    n_dev: Optional[int] = None, reverse: bool = False,
                    activation: Optional[str] = None,
                    bias: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None, **kw) -> jax.Array:
    """Fused AllGather-GEMM (call inside shard_map).  ``activation``/``bias``
    ride the kernel's tile epilogue."""
    interpret = _interpret_default() if interpret is None else interpret
    n_dev = n_dev or compat.axis_size(axis_name)
    if n_dev == 1:
        return _epilogue_by_hand(matmul(a_shard, b_local, interpret=interpret),
                                 activation, bias)
    (m_sh, k), n = a_shard.shape, b_local.shape[1]
    bm, bk, bn = _blocks(m_sh, k, n, kw)
    a = _pad_dim(_pad_dim(a_shard, 0, _SUBLANE), 1, _LANE)
    b, bias = _pad_weight(b_local, bias)
    out = _ag.ag_gemm(a, b, axis_name=axis_name, n_dev=n_dev,
                      bm=bm, bk=bk, bn=bn, reverse=reverse,
                      activation=activation, bias=bias,
                      interpret=interpret, **kw)
    if out.shape != (n_dev * m_sh, n):
        out = out.reshape(n_dev, a.shape[0], -1)[:, :m_sh, :n]
        out = out.reshape(n_dev * m_sh, n)
    return out


def matmul_rs_fused(a_local: jax.Array, b_local: jax.Array, *, axis_name: str,
                    n_dev: Optional[int] = None, reverse: bool = False,
                    activation: Optional[str] = None,
                    bias: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None, **kw) -> jax.Array:
    """Fused GEMM-ReduceScatter (call inside shard_map).  ``activation``/
    ``bias`` apply in the final reduction step's tile emit."""
    interpret = _interpret_default() if interpret is None else interpret
    n_dev = n_dev or compat.axis_size(axis_name)
    if n_dev == 1:
        return _epilogue_by_hand(matmul(a_local, b_local, interpret=interpret),
                                 activation, bias)
    m_sh, n = a_local.shape[0] // n_dev, b_local.shape[1]
    bm, bk, bn = _blocks(m_sh, a_local.shape[1], n, kw)
    a = _pad_dim(_pad_shard_rows(a_local, n_dev), 1, _LANE)
    b, bias = _pad_weight(b_local, bias)
    out = _rs.gemm_rs(a, b, axis_name=axis_name, n_dev=n_dev,
                      bm=bm, bk=bk, bn=bn, reverse=reverse,
                      activation=activation, bias=bias,
                      interpret=interpret, **kw)
    return out[:m_sh, :n]
