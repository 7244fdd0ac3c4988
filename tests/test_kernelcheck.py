"""Seeded-violation fixtures for ``repro.analysis.kernelcheck``.

Every kernel contract class is exercised from BOTH sides: the clean mini
ring kernel (a faithful miniature of ``kernels/ag_gemm.py``'s DMA
protocol) must pass, and one deliberately broken variant per class —
unbalanced semaphore, double-written slot, wrong ring neighbor, missed
output tile, VMEM-overflowing tiling — must be detected WITH step/slot
provenance.  Plus: a green run over the in-tree kernels, the closed-form
footprint model cross-checked against a real captured call, and the proof
that ``autotune`` never prices or times a tiling the budget model rejects.

All tracing is abstract (captured grid programs replayed per rank) — no
devices, no Mosaic, no subprocesses.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro import compat
from repro.analysis import kernelcheck
from repro.analysis.kernelcheck import (
    KernelCase, VMEM_LIMIT_BYTES, check_case, flux_tile_footprint,
    ring_schedules, run_kernel_checks, tile_budget_ok, traced_vmem_bytes,
    _capture_pallas_call)

AXIS = "model"
N = 4
M_SH, K, NN = 8, 8, 8


# ---------------------------------------------------------------------------
# the mini ring kernel: ag_gemm's DMA protocol at one tile per step
# ---------------------------------------------------------------------------
def _mini_kernel(a_ref, b_ref, o_ref, a_agg, acc_ref, a_vmem, b_vmem,
                 o_vmem, local_sem, send_sem, recv_sem, copy_a, copy_b,
                 copy_o, *, axis_name, n_dev, bug=None):
    step = pl.program_id(0)
    me = jax.lax.axis_index(axis_name)
    nbr = (me + 1) % n_dev
    if bug == "wrong-neighbor":
        nbr = (me + 2) % n_dev
    owner = (me - step) % n_dev

    @pl.when(step == 0)
    def _preset_local():
        cp = compat.make_async_copy(a_ref, a_agg.at[me], local_sem)
        cp.start()
        cp.wait()

    @pl.when(step > 0)
    def _wait_arrival():
        compat.make_async_remote_copy(
            src_ref=a_agg.at[owner], dst_ref=a_agg.at[owner],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=nbr).wait_recv()

    @pl.when(step < n_dev - 1)
    def _forward():
        compat.make_async_remote_copy(
            src_ref=a_agg.at[owner], dst_ref=a_agg.at[owner],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=nbr).start()

    if bug == "double-write":
        @pl.when(step == 1)
        def _second_writer():
            compat.make_async_remote_copy(
                src_ref=a_agg.at[owner], dst_ref=a_agg.at[owner],
                send_sem=send_sem, recv_sem=recv_sem,
                device_id=nbr).start()

    ca = compat.make_async_copy(a_agg.at[owner], a_vmem, copy_a)
    cb = compat.make_async_copy(b_ref, b_vmem, copy_b)
    ca.start(); cb.start(); ca.wait(); cb.wait()
    acc_ref[...] = jnp.dot(a_vmem[...], b_vmem[...],
                           preferred_element_type=jnp.float32)

    emit = (step > 0) if bug == "missed-tile" else (step >= 0)

    @pl.when(emit)
    def _epilogue():
        o_vmem[...] = acc_ref[...].astype(o_vmem.dtype)
        m_sh = a_vmem.shape[0]
        co = compat.make_async_copy(
            o_vmem, o_ref.at[pl.ds(owner * m_sh, m_sh), :], copy_o)
        co.start(); co.wait()

    drain = (step < n_dev - 1) & (step != 0) if bug == "unbalanced-sem" \
        else (step < n_dev - 1)

    @pl.when(drain)
    def _drain_send():
        compat.make_async_remote_copy(
            src_ref=a_agg.at[owner], dst_ref=a_agg.at[owner],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=nbr).wait_send()


def _mini_ring(bug=None, acc_shape=None):
    a = jnp.zeros((M_SH, K), jnp.bfloat16)
    b = jnp.zeros((K, NN), jnp.bfloat16)
    kernel = functools.partial(_mini_kernel, axis_name=AXIS, n_dev=N,
                               bug=bug)
    scratch = [
        compat.VMEM(acc_shape or (M_SH, NN), jnp.float32),
        compat.VMEM((M_SH, K), a.dtype),
        compat.VMEM((K, NN), b.dtype),
        compat.VMEM((M_SH, NN), a.dtype),
    ] + [compat.DMA_SEM] * 6
    return compat.pallas_call(
        kernel, grid=(N,),
        in_specs=[pl.BlockSpec(memory_space=compat.ANY)] * 2,
        out_specs=(pl.BlockSpec(memory_space=compat.ANY),) * 2,
        out_shape=(jax.ShapeDtypeStruct((N * M_SH, NN), a.dtype),
                   jax.ShapeDtypeStruct((N, M_SH, K), a.dtype)),  # a_agg
        scratch_shapes=scratch)(a, b)[0]


def _case(bug=None, acc_shape=None):
    return KernelCase(label=f"mini[{bug or 'clean'}]",
                      build=lambda: _mini_ring(bug, acc_shape),
                      kind="ag", n_dev=N, reverse=False, slot_rows=M_SH)


# ---------------------------------------------------------------------------
# clean baseline + one seeded violation per contract class
# ---------------------------------------------------------------------------
def test_clean_mini_ring_passes():
    assert check_case(_case()) == []


def test_detects_unbalanced_semaphore():
    # the step-0 drain is skipped; send waits pop FIFO, so the leftover
    # (reported) send is the LAST one started — step n_dev-2
    errs = check_case(_case("unbalanced-sem"))
    hits = [e for e in errs if "unbalanced send" in e]
    assert hits, errs
    assert any(f"step={N - 2}" in e and "sem" in e for e in hits), hits
    assert len(hits) == N                       # one leftover send per rank


def test_detects_double_written_slot():
    # a second unordered DMA lands in the same in-flight a_agg slot
    errs = check_case(_case("double-write"))
    hits = [e for e in errs if "two unordered DMAs" in e]
    assert hits, errs
    # provenance: the duplicated writer fires at step 1, into work0 (a_agg)
    assert any("step=1" in e and "work0" in e for e in hits), hits


def test_detects_wrong_ring_neighbor():
    errs = check_case(_case("wrong-neighbor"))
    hits = [e for e in errs if "ring neighbor" in e]
    assert hits, errs
    # rank 0 targeted rank 2; the forward-ring reference neighbor is 1
    assert any("targets rank 2" in e and "rank 0 is 1" in e
               for e in hits), hits


def test_detects_missed_output_tile():
    # the step-0 tile (each rank's own shard rows) is never stored
    errs = check_case(_case("missed-tile"))
    hits = [e for e in errs if "coverage broken" in e]
    assert hits, errs
    assert any("rank0" in e and "(0, 0)" in e for e in hits), hits


def test_detects_vmem_overflowing_tiling():
    errs = check_case(_case(acc_shape=(3000, 2000)))    # 24 MB fp32 acc
    hits = [e for e in errs if "VMEM footprint" in e and "exceeds" in e]
    assert hits, errs


# ---------------------------------------------------------------------------
# green run over the in-tree kernels + schedule/footprint cross-checks
# ---------------------------------------------------------------------------
def test_in_tree_kernels_green():
    # all four kernels (ag_gemm, gemm_rs, flash_attention, mla_decode) x
    # both ring directions x one config's shape cells
    assert run_kernel_checks(["llama2_70b"]) == []


def test_ring_schedule_matches_overlap_reference():
    # the reference tables are pure consequences of overlap._ring_perm:
    # step-0 owner is the local shard, each hop hands it downstream
    for reverse in (False, True):
        nbr, ag, rs = ring_schedules(N, reverse)
        sgn = -1 if reverse else 1
        for me in range(N):
            assert nbr[me] == (me + sgn) % N
            assert ag[me][0] == me
            assert rs[me][N - 1] == me
            for s in range(1, N):
                assert ag[me][s] == ag[(me - sgn) % N][s - 1]


def test_footprint_model_matches_traced_call():
    # the closed form autotune prunes with must equal the captured VMEM
    # scratch bytes of the real wrappers — the two cannot drift apart
    from repro.kernels.ag_gemm import ag_gemm
    from repro.kernels.gemm_rs import gemm_rs

    box = {}
    a = jnp.zeros((32, 64), jnp.bfloat16)
    b = jnp.zeros((64, 32), jnp.bfloat16)
    bias = jnp.zeros((32,), jnp.bfloat16)
    with _capture_pallas_call(box):
        ag_gemm(a, b, axis_name=AXIS, n_dev=N, bm=16, bk=32, bn=16,
                bias=bias)
    assert traced_vmem_bytes(box["cap"]) == flux_tile_footprint(
        "ag", 16, 32, 16, dtype_bytes=2, has_bias=True)

    box = {}
    a = jnp.zeros((N * 16, 64), jnp.bfloat16)
    with _capture_pallas_call(box):
        gemm_rs(a, b, axis_name=AXIS, n_dev=N, bm=16, bk=32, bn=16)
    assert traced_vmem_bytes(box["cap"]) == flux_tile_footprint(
        "rs", 16, 32, 16, dtype_bytes=2)


def test_tile_budget_rejects_infeasible():
    assert tile_budget_ok("ag", (128, 512, 128))
    # a 4096^2 fp32 accumulator alone is 64 MB — 4x the per-core VMEM
    assert not tile_budget_ok("ag", (4096, 4096, 4096))
    assert flux_tile_footprint("ag", 4096, 4096, 4096) > VMEM_LIMIT_BYTES


# ---------------------------------------------------------------------------
# autotune pruning: infeasible tilings are never priced and never timed
# ---------------------------------------------------------------------------
def test_autotune_prunes_before_pricing(monkeypatch):
    from repro.tuning import autotune

    monkeypatch.setattr(autotune, "_FLUX_BLOCK_PREFS",
                        ((4096, 4096, 4096),))
    priced = []
    real_estimate = autotune.analytic_estimate

    def spy_estimate(kind, m, n, k, n_dev, cand, *a, **kw):
        priced.append(cand)
        return real_estimate(kind, m, n, k, n_dev, cand, *a, **kw)

    monkeypatch.setattr(autotune, "analytic_estimate", spy_estimate)
    res = autotune.tune_seam("ag", 32768, 65536, 32768, 8, measure=False,
                             seam="mlp_ag")
    assert res.pruned == 2                  # both ring directions rejected
    assert all(c.mode != "flux" for c in priced)
    assert all(r["mode"] != "flux" for r in res.table)
    for c in priced:
        if c.blocks is not None:
            assert tile_budget_ok("ag", tuple(c.blocks))


def test_autotune_prunes_before_timing(monkeypatch):
    from repro.core import ect
    from repro.tuning import autotune

    monkeypatch.setattr(autotune, "_FLUX_BLOCK_PREFS",
                        ((4096, 4096, 4096), (64, 64, 64)))
    timed = []

    def fake_bench(kind, m, n, k, n_dev, cand, dtype, **kw):
        timed.append(cand)
        return (lambda: None), ()

    monkeypatch.setattr(autotune, "_bench_callable", fake_bench)
    monkeypatch.setattr(ect, "time_fn", lambda fn, *a, **kw: 1.0)
    res = autotune.tune_seam("ag", 32768, 65536, 32768, 8, measure=True,
                             modes=("xla", "flux"), seam="mlp_ag")
    assert res.source == "measured"
    assert res.pruned == 2
    assert timed, "measured sweep must still time feasible candidates"
    for c in timed:
        if c.mode == "flux":
            assert tile_budget_ok("ag", tuple(c.blocks))


def test_check_cli_kernels_lane():
    from repro.analysis import check
    assert check.main(["--kernels", "--configs", "llama2_70b", "-q"]) == 0
