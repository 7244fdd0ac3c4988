"""Fused GEMM-ReduceScatter Pallas TPU kernel (FLUX Algorithm 1, TPU-native).

Per device:  out = shard_me( sum_over_ranks( A @ B ) ),  A: [M, K_sh] (local
K columns), B: [K_sh, N].  The reduction is *fused into the matmul epilogue*
— the fp32 accumulator of each output tile is folded with the partial tile
arriving from the upstream neighbor, then immediately DMA'd downstream
(tile-granular AlltoAll of FLUX §3.1, adapted to the ICI ring so every hop is
a single neighbor link).

Differences vs. the GPU original, by design (DESIGN.md §2):
  - FLUX scatters each tile directly to its owner (1 NVLink hop) and reduces
    with atomics / specialized warps.  On an ICI torus the bandwidth-optimal
    schedule is the ring: partials accumulate as they travel, so the "Reduce
    branch" costs one VPU add per tile and needs no atomics.
  - Tile-coordinate swizzling: rank ``me`` computes the partial for owner
    ``(me + n-1 - s) mod n`` at ring step ``s``, so at any instant the n
    in-flight buffers target n distinct owners — the ring version of FLUX's
    Fig. 7 memory-contention fix (every link busy, no converging writes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro import compat
from repro.kernels.ag_gemm import EPILOGUE_ACTS, ring_barrier


def _gemm_rs_kernel(a_ref, b_ref, *rest,           # HBM: [M,K_sh], [K_sh,N], [M/n,N]
                    axis_name: str, n_dev: int, reverse: bool,
                    bm: int, bk: int, bn: int,
                    activation=None, has_bias: bool = False,
                    barrier: bool = False):
    # epilogue hook: bias/activation fold into the FINAL reduction step's
    # tile emit (after all n partials have summed — adding earlier would
    # apply the bias once per rank).
    if has_bias:
        (bias_ref, o_ref, ws, acc_ref, a_vmem, b_vmem, stage, o_stage,
         bias_vmem, send_sem, recv_sems, copy_a, copy_b, copy_o) = rest
    else:
        bias_ref = bias_vmem = None
        (o_ref, ws, acc_ref, a_vmem, b_vmem, stage, o_stage,
         send_sem, recv_sems, copy_a, copy_b, copy_o) = rest
    step = pl.program_id(0)
    mi = pl.program_id(1)
    ni = pl.program_id(2)
    ki = pl.program_id(3)
    n_m, n_n, n_k = pl.num_programs(1), pl.num_programs(2), pl.num_programs(3)

    me = lax.axis_index(axis_name)
    sgn = -1 if reverse else 1
    nbr = lax.rem(me + sgn + n_dev, n_dev)
    # swizzle: owner of the partial we compute at this step
    owner = lax.rem(me + sgn * (n_dev - 1 - step) + 2 * n_dev, n_dev)
    m_sh = n_m * bm

    if barrier:
        @pl.when((step == 0) & (mi == 0) & (ni == 0) & (ki == 0))
        def _handshake():
            ring_barrier(axis_name, n_dev)

    # ---- contraction: accumulate A[owner rows] @ B for this tile ------------
    ca = compat.make_async_copy(
        a_ref.at[pl.ds(owner * m_sh + mi * bm, bm), pl.ds(ki * bk, bk)],
        a_vmem, copy_a)
    cb = compat.make_async_copy(
        b_ref.at[pl.ds(ki * bk, bk), pl.ds(ni * bn, bn)], b_vmem, copy_b)
    ca.start(); cb.start(); ca.wait(); cb.wait()

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_vmem[...], b_vmem[...],
                            preferred_element_type=jnp.float32)

    # ---- epilogue: fold incoming partial, forward (or emit) the tile --------
    @pl.when(ki == n_k - 1)
    def _epilogue():
        @pl.when(step > 0)
        def _fold_incoming():
            # WaitSignal for THIS tile of the in-flight buffer, then fuse the
            # reduction into the accumulator (FLUX "Reduce branch").
            compat.make_async_remote_copy(
                src_ref=ws.at[step, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                dst_ref=ws.at[step, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                send_sem=send_sem, recv_sem=recv_sems.at[step, mi, ni],
                device_id=nbr, device_id_type=compat.LOGICAL_DEVICE_ID,
            ).wait_recv()
            inc = compat.make_async_copy(
                ws.at[step, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                stage, copy_a)
            inc.start(); inc.wait()
            acc_ref[...] += stage[...].astype(jnp.float32)

        @pl.when(step < n_dev - 1)
        def _forward_tile():
            stage[...] = acc_ref[...].astype(stage.dtype)
            st = compat.make_async_copy(
                stage, ws.at[step, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                copy_o)
            st.start(); st.wait()
            compat.make_async_remote_copy(
                src_ref=ws.at[step, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                dst_ref=ws.at[step + 1, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                send_sem=send_sem, recv_sem=recv_sems.at[step + 1, mi, ni],
                device_id=nbr, device_id_type=compat.LOGICAL_DEVICE_ID,
            ).start()

        @pl.when(step == n_dev - 1)
        def _emit():
            # final step computes OUR shard (owner == me): write the reduced
            # tile straight to the output — epilogue fusion, no extra pass.
            acc = acc_ref[...]
            if has_bias:
                cbias = compat.make_async_copy(
                    bias_ref.at[:, pl.ds(ni * bn, bn)], bias_vmem, copy_b)
                cbias.start(); cbias.wait()
                acc = acc + bias_vmem[...].astype(jnp.float32)
            if activation is not None:
                acc = EPILOGUE_ACTS[activation](acc)
            o_stage[...] = acc.astype(o_stage.dtype)
            co = compat.make_async_copy(
                o_stage, o_ref.at[pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)], copy_o)
            co.start(); co.wait()

        # drain one outstanding tile-send per tile from the previous step so
        # the semaphore balances by kernel exit.
        @pl.when(step > 0)
        def _drain_prev_send():
            compat.make_async_remote_copy(
                src_ref=ws.at[step - 1, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                dst_ref=ws.at[step, pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)],
                send_sem=send_sem, recv_sem=recv_sems.at[step, mi, ni],
                device_id=nbr, device_id_type=compat.LOGICAL_DEVICE_ID,
            ).wait_send()


def gemm_rs(a_local: jax.Array, b_local: jax.Array, *, axis_name: str,
            n_dev: int, bm: int = 256, bk: int = 512, bn: int = 256,
            reverse: bool = False, out_dtype=None, partial_dtype=None,
            activation: str | None = None, bias: jax.Array | None = None,
            interpret: bool | None = None, collective_id: int = 1) -> jax.Array:
    """out[M/n, N] = act(ReduceScatter_m(A_local @ B_local) + bias), fused.
    Call inside shard_map; A column(K)-sharded, B row(K)-sharded over
    ``axis_name``.  ``activation``/``bias`` apply in the final reduction
    step's tile emit (bias: [N])."""
    m, k_sh = a_local.shape
    k2, n = b_local.shape
    assert k_sh == k2
    assert m % n_dev == 0, (m, n_dev)
    assert activation is None or activation in EPILOGUE_ACTS, activation
    interpret = compat.interpret_default() if interpret is None else interpret
    m_sh = m // n_dev
    out_dtype = out_dtype or a_local.dtype
    partial_dtype = partial_dtype or out_dtype
    bm, bk, bn = min(bm, m_sh), min(bk, k_sh), min(bn, n)
    assert m_sh % bm == 0 and k_sh % bk == 0 and n % bn == 0, (
        f"gemm_rs dims ({m_sh},{k_sh},{n}) vs blocks ({bm},{bk},{bn})")
    grid = (n_dev, m_sh // bm, n // bn, k_sh // bk)
    has_bias = bias is not None
    kernel = functools.partial(
        _gemm_rs_kernel, axis_name=axis_name, n_dev=n_dev, reverse=reverse,
        bm=bm, bk=bk, bn=bn, activation=activation, has_bias=has_bias,
        barrier=not interpret)
    in_specs = [pl.BlockSpec(memory_space=compat.ANY),
                pl.BlockSpec(memory_space=compat.ANY)]
    operands = [a_local, b_local]
    scratch = [
        compat.VMEM((bm, bn), jnp.float32),          # accumulator
        compat.VMEM((bm, bk), a_local.dtype),
        compat.VMEM((bk, bn), b_local.dtype),
        compat.VMEM((bm, bn), partial_dtype),        # stage/cast buffer
        compat.VMEM((bm, bn), out_dtype),            # output cast buffer
    ]
    if has_bias:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(pl.BlockSpec(memory_space=compat.ANY))
        operands.append(bias.reshape(1, n))
        scratch.append(compat.VMEM((1, bn), bias.dtype))        # bias tile
    scratch += [
        compat.DMA_SEM,
        # one arrival semaphore per in-flight tile: a wait is satisfied only
        # by the tile it waits for, whatever order the upstream's tiles land
        compat.SemaphoreType.DMA((n_dev, m_sh // bm, n // bn)),
        compat.DMA_SEM, compat.DMA_SEM, compat.DMA_SEM,
    ]
    # the in-flight partials are a second output, dropped here: Mosaic
    # allocates scratch only in VMEM, SMEM and semaphores
    return compat.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec(memory_space=compat.ANY),) * 2,
        out_shape=(jax.ShapeDtypeStruct((m_sh, n), out_dtype),
                   jax.ShapeDtypeStruct((n_dev, m_sh, n), partial_dtype)),
        scratch_shapes=scratch,
        compiler_params=compat.pallas_compiler_params(
            collective_id=None if interpret else collective_id),
        interpret=interpret,
    )(*operands)[0]
