"""Fused AllGather-GEMM Pallas TPU kernel (FLUX Algorithm 2/3, TPU-native).

One kernel per device computes  C = AllGather_m(A_shard) @ B_local  while the
gather itself rides the ICI ring *inside* the kernel:

  - ``a_agg`` is the aggregated HBM buffer of FLUX Algorithm 2 (one slot per
    rank; the local slot is "preset" — paper: local signals preset to true).
  - grid axis 0 is the ring step; at step ``s`` the kernel multiplies the
    shard owned by rank ``(me - s) mod n`` (tile-coordinate swizzle: every
    device walks a different output row region each step, §4.1) while the
    NEXT shard is already in flight from the left neighbor.
  - FLUX's host-side ``DataTransfer + SetSignal`` (Algorithm 3) becomes an
    in-kernel ``make_async_remote_copy``; ``WaitSignal`` becomes the DMA recv
    semaphore wait.  No host in the loop, no spin-waiting.
  - each slot is written by exactly one DMA -> no write-after-read hazards,
    no flow-control acks needed (this is why the full A_agg buffer exists in
    FLUX too).

Ring order starts after the local rank (paper §4.3: "ring order starting
after the local rank").  ``reverse=True`` flips the ring direction — the TPU
analogue of the paper's pull/push tuning knob.

Epilogue hook (FLUX thesis: fuse MORE dependent compute into the kernel):
``activation`` / ``bias`` apply to the fp32 accumulator in the TILE epilogue
— bias is DMA'd per output-column tile and added, the activation runs on the
VPU before the cast+store, so the fused elementwise tail costs no extra HBM
pass.  Driven by ``overlap.FusedOp`` via ``kernels.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro import compat
# one activation registry for the whole overlap surface (overlap.Epilogue
# validation and the kernel tile epilogues must never drift apart; overlap
# imports kernels only lazily, so this edge is cycle-free)
from repro.core.overlap import ACTIVATIONS as EPILOGUE_ACTS


def ring_barrier(axis_name: str, n_dev: int) -> None:
    """Handshake with both ring neighbours before the first remote DMA: a
    device may write into a neighbour's scratch only once that neighbour
    has entered the kernel.  Mosaic only: JAX's generic interpreter cannot
    lower a barrier semaphore, and it turns each remote DMA into
    collectives that already synchronize every device."""
    me = lax.axis_index(axis_name)
    sem = compat.barrier_semaphore()
    for hop in (1, n_dev - 1):
        compat.semaphore_signal(sem, device_id=lax.rem(me + hop, n_dev),
                                device_id_type=compat.LOGICAL_DEVICE_ID)
    compat.semaphore_wait(sem, 2)


def _ag_gemm_kernel(a_ref, b_ref, *rest,           # HBM: [M_sh,K], [K,N], [n*M_sh,N]
                    axis_name: str, n_dev: int, reverse: bool,
                    bm: int, bk: int, bn: int,
                    activation=None, has_bias: bool = False,
                    barrier: bool = False):
    if has_bias:
        (bias_ref, o_ref, a_agg, acc_ref, a_vmem, b_vmem, o_vmem, bias_vmem,
         local_sem, send_sem, recv_sems, copy_a, copy_b, copy_o) = rest
    else:
        bias_ref = bias_vmem = None
        (o_ref, a_agg, acc_ref, a_vmem, b_vmem, o_vmem,
         local_sem, send_sem, recv_sems, copy_a, copy_b, copy_o) = rest
    step = pl.program_id(0)
    mi = pl.program_id(1)
    ni = pl.program_id(2)
    ki = pl.program_id(3)
    n_m, n_n, n_k = pl.num_programs(1), pl.num_programs(2), pl.num_programs(3)
    first_inner = (mi == 0) & (ni == 0) & (ki == 0)

    me = lax.axis_index(axis_name)
    sgn = -1 if reverse else 1
    nbr = lax.rem(me + sgn + n_dev, n_dev)            # downstream neighbor
    owner = lax.rem(me - sgn * step + 2 * n_dev, n_dev)  # whose shard we hold now
    nxt = lax.rem(me - sgn * (step + 1) + 2 * n_dev, n_dev)

    # ---- step 0 bootstrap: stage the local shard into its A_agg slot -------
    @pl.when((step == 0) & first_inner)
    def _preset_local():
        if barrier:
            ring_barrier(axis_name, n_dev)
        cp = compat.make_async_copy(a_ref, a_agg.at[me], local_sem)
        cp.start()
        cp.wait()

    # ---- ring: forward the shard we hold to the downstream neighbor --------
    @pl.when(first_inner)
    def _ring():
        @pl.when(step > 0)
        def _wait_arrival():
            # WaitSignal: the DMA landing in slot `owner` was issued by the
            # upstream neighbor during its previous step.
            compat.make_async_remote_copy(
                src_ref=a_agg.at[owner], dst_ref=a_agg.at[owner],
                send_sem=send_sem, recv_sem=recv_sems.at[owner],
                device_id=nbr, device_id_type=compat.LOGICAL_DEVICE_ID,
            ).wait_recv()

        @pl.when(step < n_dev - 1)
        def _forward():
            compat.make_async_remote_copy(
                src_ref=a_agg.at[owner], dst_ref=a_agg.at[owner],
                send_sem=send_sem, recv_sem=recv_sems.at[owner],
                device_id=nbr, device_id_type=compat.LOGICAL_DEVICE_ID,
            ).start()

    # ---- MXU block matmul over the current shard ---------------------------
    ca = compat.make_async_copy(
        a_agg.at[owner, pl.ds(mi * bm, bm), pl.ds(ki * bk, bk)], a_vmem, copy_a)
    cb = compat.make_async_copy(
        b_ref.at[pl.ds(ki * bk, bk), pl.ds(ni * bn, bn)], b_vmem, copy_b)
    ca.start(); cb.start(); ca.wait(); cb.wait()

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_vmem[...], b_vmem[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        # fused tile epilogue: bias + activation on the fp32 accumulator,
        # then the swizzled store (rows of the shard we currently hold)
        acc = acc_ref[...]
        if has_bias:
            cbias = compat.make_async_copy(
                bias_ref.at[:, pl.ds(ni * bn, bn)], bias_vmem, copy_b)
            cbias.start(); cbias.wait()
            acc = acc + bias_vmem[...].astype(jnp.float32)
        if activation is not None:
            acc = EPILOGUE_ACTS[activation](acc)
        o_vmem[...] = acc.astype(o_vmem.dtype)
        co = compat.make_async_copy(
            o_vmem, o_ref.at[pl.ds(owner * n_m * bm + mi * bm, bm),
                             pl.ds(ni * bn, bn)], copy_o)
        co.start(); co.wait()

    # ---- drain: make sure our forward completed before the kernel exits ----
    @pl.when((step < n_dev - 1) & (mi == n_m - 1) & (ni == n_n - 1)
             & (ki == n_k - 1))
    def _drain_send():
        compat.make_async_remote_copy(
            src_ref=a_agg.at[owner], dst_ref=a_agg.at[owner],
            send_sem=send_sem, recv_sem=recv_sems.at[owner],
            device_id=nbr, device_id_type=compat.LOGICAL_DEVICE_ID,
        ).wait_send()


def ag_gemm(a_shard: jax.Array, b_local: jax.Array, *, axis_name: str,
            n_dev: int, bm: int = 256, bk: int = 512, bn: int = 256,
            reverse: bool = False, out_dtype=None,
            activation: str | None = None, bias: jax.Array | None = None,
            interpret: bool | None = None, collective_id: int = 0) -> jax.Array:
    """C[n*M_sh, N_local] = act(AllGather(A_shard) @ B_local + bias), fused.
    Call inside shard_map; A row-sharded over ``axis_name``, B
    column-sharded.  ``activation``/``bias`` are the tile-epilogue hook
    (None -> plain GEMM; bias: [N_local])."""
    m_sh, k = a_shard.shape
    k2, n = b_local.shape
    assert k == k2
    assert activation is None or activation in EPILOGUE_ACTS, activation
    interpret = compat.interpret_default() if interpret is None else interpret
    out_dtype = out_dtype or a_shard.dtype
    bm, bk, bn = min(bm, m_sh), min(bk, k), min(bn, n)
    assert m_sh % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"ag_gemm dims ({m_sh},{k},{n}) vs blocks ({bm},{bk},{bn})")
    grid = (n_dev, m_sh // bm, n // bn, k // bk)
    has_bias = bias is not None
    kernel = functools.partial(
        _ag_gemm_kernel, axis_name=axis_name, n_dev=n_dev, reverse=reverse,
        bm=bm, bk=bk, bn=bn, activation=activation, has_bias=has_bias,
        barrier=not interpret)
    in_specs = [pl.BlockSpec(memory_space=compat.ANY),
                pl.BlockSpec(memory_space=compat.ANY)]
    operands = [a_shard, b_local]
    scratch = [
        compat.VMEM((bm, bn), jnp.float32),          # accumulator
        compat.VMEM((bm, bk), a_shard.dtype),
        compat.VMEM((bk, bn), b_local.dtype),
        compat.VMEM((bm, bn), out_dtype),
    ]
    if has_bias:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(pl.BlockSpec(memory_space=compat.ANY))
        operands.append(bias.reshape(1, n))
        scratch.append(compat.VMEM((1, bn), bias.dtype))       # bias tile
    scratch += [
        compat.DMA_SEM, compat.DMA_SEM,
        # one arrival semaphore per A_agg slot: a wait is satisfied only by
        # the shard it waits for, even if the upstream rank runs a step
        # ahead and a later shard lands first
        compat.SemaphoreType.DMA((n_dev,)),
        compat.DMA_SEM, compat.DMA_SEM, compat.DMA_SEM,
    ]
    # A_agg is a second output, dropped here: Mosaic allocates scratch only
    # in VMEM, SMEM and semaphores, and the gathered shards need HBM
    return compat.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec(memory_space=compat.ANY),) * 2,
        out_shape=(jax.ShapeDtypeStruct((n_dev * m_sh, n), out_dtype),
                   jax.ShapeDtypeStruct((n_dev, m_sh, k), a_shard.dtype)),
        scratch_shapes=scratch,
        compiler_params=compat.pallas_compiler_params(
            collective_id=None if interpret else collective_id),
        interpret=interpret,
    )(*operands)[0]
