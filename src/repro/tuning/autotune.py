"""Measured + analytic per-seam autotuner (paper §4.4).

For one seam (collective kind + GEMM shape) the tuner enumerates candidate
``(mode, comm_chunks, reverse, bm/bk/bn)`` settings, scores each one, and
returns the winner as a ``SeamPlan``:

  * **measured** — a jitted sweep of the real overlap op on the current
    devices (shard_mapped over ``n_dev`` devices when available, the
    single-device fallback otherwise); median wall time via ``ect.time_fn``.
  * **analytic** — the ``core.ect`` roofline.  Used when measurement is
    meaningless: fewer devices than ``n_dev``, or Pallas interpret mode
    (``REPRO_PALLAS_INTERPRET=1``), where kernel timings reflect the
    interpreter, not hardware.

``measure="auto"`` picks between the two; ``True``/``False`` force them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import ect
from repro.tuning.plans import PlanSet, SeamPlan

# candidate modes per collective kind (wire precision is a SEPARATE knob —
# ``Candidate.wire_dtype`` — swept orthogonally over the transports that
# can carry a quantized payload; see ``wire_supported``):
_KIND_MODES: Dict[str, Tuple[str, ...]] = {
    "ag": ("xla", "decomposed", "decomposed_bidir", "flux"),
    "rs": ("xla", "decomposed", "decomposed_bidir", "flux"),
    "ar": ("xla", "decomposed"),
    # MoE EP exchange: barrier all_to_alls vs the interleaved ppermute ring
    # (chunk count x direction swept; no flux kernel)
    "a2a": ("xla", "decomposed"),
}
# flux block-preference sweep (the CUTLASS-template-parameter analogue)
_FLUX_BLOCK_PREFS: Tuple[Tuple[int, int, int], ...] = (
    (256, 512, 256), (128, 512, 128), (512, 512, 512))

# the wire dtypes the tuner sweeps when low precision is allowed
WIRE_DTYPE_SWEEP: Tuple[Optional[str], ...] = (None, "int8", "fp8_e4m3",
                                               "int4")


def wire_supported(kind: str, mode: str, scatter_axis: str = "seq") -> bool:
    """Whether (kind, mode, layout) actually carries a quantized payload:
    flux has no quantized DMA path; xla's psum collectives (rs/ar) cannot
    carry per-block scales; ag/hidden has no collective at all."""
    if mode == "flux":
        return False
    if kind == "ag":
        return scatter_axis != "hidden"
    if kind == "a2a":
        return True
    # rs (incl. rs/hidden == ar) and ar: ring transports only
    return mode.startswith("decomposed")


@dataclasses.dataclass(frozen=True)
class Candidate:
    mode: str
    comm_chunks: int
    reverse: bool
    blocks: Optional[Tuple[int, int, int]] = None
    shared_gather: bool = True        # one ring pass for N-weight gathers
    fuse_epilogue: bool = True        # epilogue inside the overlapped loop
    scatter_axis: str = "seq"         # residual-stream layout (seq | hidden)
    wire_dtype: Optional[str] = None  # forward-wire precision (overlap
    #                                   VALID_WIRE_DTYPES; None = fp wire)


@dataclasses.dataclass
class TuneResult:
    seam: str                         # model seam name (or the kind itself)
    kind: str                         # ag | rs | ar
    m: int
    n: int
    k: int
    n_dev: int
    plan: SeamPlan
    table: List[Dict]                 # one row per candidate (see tune_seam)
    source: str                       # measured | analytic
    pruned: int = 0                   # flux tilings rejected by the static
    #                                   VMEM budget before pricing/timing


def _ring_chunk_options(n_dev: int) -> Tuple[int, ...]:
    # no 0 ("auto"): auto IS n_dev in every ring op, and duplicate
    # candidates would be compiled and timed twice on the measured path
    return (n_dev, 2 * n_dev, 4 * n_dev)


def candidate_space(kind: str, m: int, n: int, k: int, n_dev: int,
                    *, allow_flux: bool = True, allow_q8: bool = True,
                    modes: Optional[Sequence[str]] = None,
                    wire_dtypes: Optional[Sequence[Optional[str]]] = None,
                    n_weights: int = 1,
                    epilogue: bool = False,
                    scatter_axis: str = "seq") -> List[Candidate]:
    """All tunable settings for one seam kind.  ``modes`` restricts the mode
    set (used by the measured path to drop flux under interpret mode).

    ``wire_dtypes`` is the wire-precision sweep (None entries = fp wire);
    the default derives from the deprecated ``allow_q8`` flag — ``True``
    sweeps ``(None, "int8")`` (the old q8-mode pair), ``False`` keeps the
    fp wire only.  Pass ``WIRE_DTYPE_SWEEP`` for the full set.  Quantized
    wires are only emitted for transports that carry them
    (``wire_supported``).

    ``n_weights > 1`` additionally sweeps ``shared_gather`` (one ring pass
    vs one per weight) and ``epilogue=True`` sweeps ``fuse_epilogue``
    (elementwise tail inside vs after the overlapped loop) — the FusedOp
    fusion knobs.  Only the transports that CONSUME a knob sweep it: xla's
    monolithic gather is shared and its epilogue XLA-fused regardless, and
    rs/ar epilogues run once on the reduced output either way, so sweeping
    there would score byte-identical programs under different labels.

    ``scatter_axis`` fixes the residual-stream layout the seam runs under
    (it is swept JOINTLY at the model level by ``autotune_model``, never
    per seam — a per-seam layout split would be incoherent).  Under
    "hidden" an AG seam has NO collective (one candidate) and an RS seam
    behaves like the "ar" kind (contraction-chunked AllReduce)."""
    from repro.core.overlap import normalize_mode
    from repro.kernels.ops import plan_blocks
    if wire_dtypes is None:
        wire_dtypes = (None, "int8") if allow_q8 else (None,)
    hidden = scatter_axis == "hidden"
    if kind == "ag" and hidden:
        # input already replicated: no transport to tune
        return [Candidate("xla", 0, False, scatter_axis="hidden")]
    mode_kind = "ar" if (kind == "rs" and hidden) else kind
    sweep_sg = kind == "ag" and n_weights > 1
    sweep_fe = kind == "ag" and epilogue
    fusion_opts = [(sg, fe)
                   for sg in ((True, False) if sweep_sg else (True,))
                   for fe in ((True, False) if sweep_fe else (True,))]
    out: List[Candidate] = []
    for mode in (modes or _KIND_MODES[mode_kind]):
        mode, _ = normalize_mode(mode)     # accept deprecated spellings
        if mode == "flux" and not allow_flux:
            continue
        if mode == "xla":
            out.append(Candidate(mode, 0, False, scatter_axis=scatter_axis))
            continue
        if mode == "flux":
            # per-device GEMM shape (paper §4.4: tiling is not bound to N_TP)
            if kind == "ag":
                gm, gk, gn = max(m // n_dev, 1), k, max(n // n_dev, 1)
            else:
                gm, gk, gn = max(m // n_dev, 1), max(k // n_dev, 1), n
            for pref in _FLUX_BLOCK_PREFS:
                blocks = plan_blocks(gm, gk, gn, *pref)
                for reverse in (False, True):
                    for sg, fe in fusion_opts:
                        out.append(Candidate(mode, 0, reverse, blocks,
                                             shared_gather=sg,
                                             fuse_epilogue=fe,
                                             scatter_axis=scatter_axis))
            continue
        # ring modes: chunk count x direction (AR chunks the contraction —
        # no ring, so no direction; bidir already rides both directions)
        for chunks in _ring_chunk_options(n_dev):
            for reverse in (False, True):
                if reverse and (mode_kind == "ar"
                                or mode == "decomposed_bidir"):
                    continue
                for sg, fe in fusion_opts:
                    out.append(Candidate(mode, chunks, reverse,
                                         shared_gather=sg, fuse_epilogue=fe,
                                         scatter_axis=scatter_axis))
    # expand over the wire-precision sweep (quantized wires only where the
    # transport actually carries them)
    expanded: List[Candidate] = []
    for c in out:
        for wd in wire_dtypes:
            if wd is not None and not wire_supported(kind, c.mode,
                                                     c.scatter_axis):
                continue
            expanded.append(dataclasses.replace(c, wire_dtype=wd))
    # dedupe (plan_blocks may collapse block prefs on small shapes)
    seen, uniq = set(), []
    for c in expanded:
        key = (c.mode, c.comm_chunks, c.reverse, c.blocks, c.shared_gather,
               c.fuse_epilogue, c.scatter_axis, c.wire_dtype)
        if key not in seen:
            seen.add(key)
            uniq.append(c)
    return uniq


def prune_infeasible(kind: str, cands: List[Candidate],
                     *, dtype_bytes: int = 2, epilogue: bool = False
                     ) -> Tuple[List[Candidate], List[Candidate]]:
    """(kept, pruned): drop flux candidates whose static VMEM footprint the
    ``kernelcheck`` tile-budget model rejects.  Runs BEFORE any pricing or
    timing — ``ect`` never models an infeasible tiling and the measured
    path never compiles one (ISSUE 9 satellite: the sweep previously timed
    arbitrary ``bm/bk/bn`` with no validity filter)."""
    if kind not in ("ag", "rs"):
        return list(cands), []
    from repro.analysis.kernelcheck import tile_budget_ok   # lazy: no cycle
    keep: List[Candidate] = []
    pruned: List[Candidate] = []
    for c in cands:
        if (c.mode == "flux" and c.blocks is not None
                and not tile_budget_ok(kind, tuple(c.blocks),
                                       dtype_bytes=dtype_bytes,
                                       has_bias=epilogue)):
            pruned.append(c)
        else:
            keep.append(c)
    return keep, pruned


def analytic_estimate(kind: str, m: int, n: int, k: int, n_dev: int,
                      cand: Candidate, dtype_bytes: int = 2,
                      n_weights: int = 1, epilogue: bool = False,
                      full: bool = False):
    """Roofline OverallTime for one candidate (``full=True`` returns the
    whole ``ect.model_overlap`` dict — bytes-on-wire etc.)."""
    est = ect.model_overlap(kind, m, n, k, n_dev, cand.mode, dtype_bytes,
                            comm_chunks=cand.comm_chunks,
                            n_weights=n_weights,
                            shared_gather=cand.shared_gather,
                            epilogue=epilogue,
                            fuse_epilogue=cand.fuse_epilogue,
                            scatter_axis=cand.scatter_axis,
                            wire_dtype=cand.wire_dtype)
    return est if full else est["overall"]


# ---------------------------------------------------------------------------
# measured path
# ---------------------------------------------------------------------------
def _round_to(x: int, mult: int) -> int:
    return max(mult, x - x % mult)


def _bench_epilogue(kind: str, n_weights: int, epilogue: bool):
    """The representative Epilogue benched for a seam: the gated-FFN pair
    for two-weight AG seams, a plain activation otherwise."""
    from repro.core.overlap import Epilogue
    if kind == "a2a":
        # the EP exchange op REQUIRES the pure gated pair (its backward
        # differentiates the expert SwiGLU as one closure)
        return Epilogue(activation="silu", gate="pair")
    if not epilogue:
        return Epilogue()
    if kind == "ag" and n_weights == 2:
        return Epilogue(activation="silu", gate="pair")
    return Epilogue(activation="silu")


def _bench_callable(kind: str, m: int, n: int, k: int, n_dev: int,
                    cand: Candidate, dtype, n_weights: int = 1,
                    epilogue: bool = False):
    """(jitted_fn, args) timing one FusedOp under ``cand``'s settings.
    Shard_maps over ``n_dev`` devices when the host has them; otherwise the
    single-device fallback path (still times the real GEMM)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro import compat
    from repro.core.overlap import FusedOp

    multi = n_dev > 1 and len(jax.devices()) >= n_dev
    axis = "tune" if multi else None
    m = _round_to(m, n_dev)
    n = _round_to(n, n_dev)
    k = _round_to(k, n_dev)
    key = jax.random.PRNGKey(0)

    if kind == "a2a":
        # EP exchange: local [ep, e_loc, cap, k=d_model] dispatch buffer and
        # the global (w1, w3, w2) expert stacks (m routed rows per device,
        # n = expert_ffn).  Global buffer dim 0 carries both the shard and
        # the destination-rank dims (n_dev * n_dev).
        e_loc = 2
        cap = max(m // (n_dev * e_loc), 1)
        x = jax.random.normal(key, (n_dev * n_dev, e_loc, cap, k), dtype)
        ws = (jax.random.normal(jax.random.PRNGKey(1),
                                (n_dev * e_loc, k, n), dtype) / k ** 0.5,
              jax.random.normal(jax.random.PRNGKey(2),
                                (n_dev * e_loc, k, n), dtype) / k ** 0.5,
              jax.random.normal(jax.random.PRNGKey(3),
                                (n_dev * e_loc, n, k), dtype) / n ** 0.5)
        fused = FusedOp(kind="a2a", axis=(axis,) if axis else (),
                        mode=cand.mode, comm_chunks=cand.comm_chunks,
                        reverse=cand.reverse,
                        epilogue=_bench_epilogue(kind, 3, True), n_weights=3,
                        wire_dtype=cand.wire_dtype)
        if not multi:
            return jax.jit(lambda a, *bs: fused(a, *bs)), (x, *ws)
        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("tune",))
        fn = compat.shard_map(lambda a, *bs: fused(a, *bs), mesh=mesh,
                              in_specs=(P(axis),) * 4, out_specs=P(axis),
                              check_vma=False)
        return jax.jit(fn), (x, *ws)

    x = jax.random.normal(key, (1, m, k), dtype)
    nw = n_weights if kind == "ag" else 1
    ws = tuple(jax.random.normal(jax.random.PRNGKey(1 + i), (k, n), dtype)
               / k ** 0.5 for i in range(nw))
    hidden = cand.scatter_axis == "hidden"
    fused = FusedOp(kind=kind, axis=axis, mode=cand.mode,
                    comm_chunks=cand.comm_chunks, reverse=cand.reverse,
                    blocks=cand.blocks,
                    epilogue=_bench_epilogue(kind, nw, epilogue),
                    n_weights=nw, fuse_epilogue=cand.fuse_epilogue,
                    shared_gather=cand.shared_gather,
                    scatter_axis=cand.scatter_axis,
                    wire_dtype=cand.wire_dtype)
    if kind == "ag":
        # hidden layout: the activation arrives replicated (no gather)
        x_spec = P(None, None, None) if hidden else P(None, axis, None)
        in_specs = (x_spec,) + (P(None, axis),) * nw
        out_spec = (P(None, None, axis) if fused.combines
                    else (P(None, None, axis),) * nw)
    else:           # rs / ar share operand sharding; ar (and rs/hidden)
        #             replicate the output
        in_specs = (P(None, None, axis), P(axis, None))
        out_spec = (P(None, axis, None) if kind == "rs" and not hidden
                    else P(None, None, None))

    if not multi:
        return jax.jit(lambda a, *bs: fused(a, *bs)), (x, *ws)

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("tune",))
    fn = compat.shard_map(lambda a, *bs: fused(a, *bs), mesh=mesh,
                          in_specs=in_specs, out_specs=out_spec,
                          check_vma=False)
    return jax.jit(fn), (x, *ws)


def _measurable_modes(kind: str, allow_flux: bool) -> Tuple[str, ...]:
    from repro import compat
    modes = _KIND_MODES[kind]
    # interpret-mode Pallas timings measure the interpreter, not hardware —
    # keep flux out of the measured sweep there (it still competes via the
    # analytic path on real devices).
    if compat.interpret_default():
        modes = tuple(md for md in modes if md != "flux")
    if not allow_flux:
        modes = tuple(md for md in modes if md != "flux")
    return modes


def tune_seam(kind: str, m: int, n: int, k: int, n_dev: int,
              *, dtype_bytes: int = 2, allow_flux: bool = True,
              allow_q8: bool = True, measure="auto",
              modes: Optional[Sequence[str]] = None,
              wire_dtypes: Optional[Sequence[Optional[str]]] = None,
              max_logit_rmse: Optional[float] = None,
              rmse_fn=None,
              seam: Optional[str] = None, iters: int = 3,
              warmup: int = 1, n_weights: int = 1,
              epilogue: bool = False,
              scatter_axis: str = "seq") -> TuneResult:
    """Tune one seam.  Returns the winning plan plus the full candidate
    table (``table`` rows: mode/comm_chunks/reverse/blocks/shared_gather/
    fuse_epilogue/scatter_axis/wire_dtype/comm_bytes/predicted_s/
    logit_rmse/within_budget and, on the measured path, measured_s).

    Wire precision is tuned under an ERROR BUDGET, not time alone: every
    quantized candidate is scored by ``rmse_fn(kind, m, n, k, n_dev,
    wire_dtype)`` (default: ``error_budget.seam_wire_rmse``, the seeded
    proxy deviation vs the fp wire) and candidates whose deviation exceeds
    ``max_logit_rmse`` are kept in the table (``within_budget=False``) but
    can never win.  ``max_logit_rmse=None`` disables the filter (the fp
    wire scores 0.0 and is always eligible).

    ``n_weights``/``epilogue`` describe the FusedOp the seam will run
    (e.g. the gated FFN's two-weight silu-gate) so the fusion knobs are
    swept too; ``scatter_axis`` fixes the residual layout the seam is
    tuned UNDER (the layout itself is a model-level decision — see
    ``autotune_model``)."""
    assert kind in _KIND_MODES, kind
    if measure == "auto":
        import jax
        from repro import compat
        measure = (n_dev > 1 and len(jax.devices()) >= n_dev
                   and not compat.interpret_default())
    if rmse_fn is None:
        from repro.tuning.error_budget import seam_wire_rmse
        rmse_fn = seam_wire_rmse

    def row(c, measured=0.0):
        est = analytic_estimate(kind, m, n, k, n_dev, c, dtype_bytes,
                                n_weights, epilogue, full=True)
        rmse = (rmse_fn(kind, m, n, k, n_dev, c.wire_dtype)
                if c.wire_dtype else 0.0)
        return {"mode": c.mode, "comm_chunks": c.comm_chunks,
                "reverse": c.reverse, "blocks": c.blocks,
                "shared_gather": c.shared_gather,
                "fuse_epilogue": c.fuse_epilogue,
                "scatter_axis": c.scatter_axis,
                "wire_dtype": c.wire_dtype,
                "comm_bytes": est["comm_bytes"],
                "predicted_s": est["overall"],
                "logit_rmse": rmse,
                "within_budget": (max_logit_rmse is None
                                  or rmse <= max_logit_rmse),
                "measured_s": measured}

    def pick(table, score):
        eligible = [r for r in table if r["within_budget"]]
        return min(eligible or table, key=score)

    mode_kind = "ar" if (kind == "rs" and scatter_axis == "hidden") else kind
    if measure:
        import jax.numpy as jnp
        dtype = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
        cands = candidate_space(kind, m, n, k, n_dev, allow_flux=allow_flux,
                                allow_q8=allow_q8,
                                modes=modes or _measurable_modes(mode_kind,
                                                                 allow_flux),
                                wire_dtypes=wire_dtypes,
                                n_weights=n_weights, epilogue=epilogue,
                                scatter_axis=scatter_axis)
        cands, dropped = prune_infeasible(kind, cands,
                                          dtype_bytes=dtype_bytes,
                                          epilogue=epilogue)
        table = []
        for c in cands:
            fn, args = _bench_callable(kind, m, n, k, n_dev, c, dtype,
                                       n_weights=n_weights,
                                       epilogue=epilogue)
            t = ect.time_fn(fn, *args, iters=iters, warmup=warmup)
            table.append(row(c, measured=t))
        best = pick(table, lambda r: r["measured_s"])
        source = "measured"
    else:
        cands = candidate_space(kind, m, n, k, n_dev, allow_flux=allow_flux,
                                allow_q8=allow_q8, modes=modes,
                                wire_dtypes=wire_dtypes,
                                n_weights=n_weights, epilogue=epilogue,
                                scatter_axis=scatter_axis)
        cands, dropped = prune_infeasible(kind, cands,
                                          dtype_bytes=dtype_bytes,
                                          epilogue=epilogue)
        table = [row(c) for c in cands]
        best = pick(table, lambda r: r["predicted_s"])
        source = "analytic"

    blocks = best["blocks"]
    if blocks is None:
        from repro.kernels.ops import plan_blocks
        if kind == "ag":
            blocks = plan_blocks(max(m // n_dev, 1), k, max(n // n_dev, 1))
        else:
            blocks = plan_blocks(max(m // n_dev, 1), max(k // n_dev, 1), n)
    plan = SeamPlan(mode=best["mode"], comm_chunks=best["comm_chunks"],
                    reverse=best["reverse"], blocks=tuple(blocks),
                    shared_gather=best["shared_gather"],
                    fuse_epilogue=best["fuse_epilogue"],
                    scatter_axis=best["scatter_axis"],
                    wire_dtype=best["wire_dtype"],
                    source=source, predicted_s=best["predicted_s"],
                    measured_s=best["measured_s"],
                    logit_rmse=best["logit_rmse"]).validate()
    return TuneResult(seam=seam or kind, kind=kind, m=m, n=n, k=k,
                      n_dev=n_dev, plan=plan, table=table, source=source,
                      pruned=len(dropped))


# ---------------------------------------------------------------------------
# whole-model tuning
# ---------------------------------------------------------------------------
def serving_decode_batch() -> int:
    """The decode-AR seam's m dimension under the serving runtime: the
    Server jits ``decode_step`` at ``ServeConfig.max_batch`` rows, so plans
    tuned for any other batch would miss the server's actual signature."""
    from repro.runtime.server import ServeConfig
    return ServeConfig().max_batch


def model_seam_shapes(cfg, par, tokens_per_dp: int = 2048,
                      decode_batch: Optional[int] = None
                      ) -> Dict[str, Tuple[str, int, int, int]]:
    """(kind, m, n, k) per model seam SHAPE CELL, from the arch's padded
    GEMM shapes.

    Keys are seam names, cell-qualified (``"<seam>@<cell>"``, mirroring the
    dryrun cell naming) when one model seam runs several distinct GEMM
    shapes: MLA's attention AG seam drives TWO up-projections with very
    different widths (``attn_ag@q_up``: q_lora_rank -> heads*(nope+rope)
    vs ``attn_ag@kv_up``: kv_lora_rank -> heads*(nope+v)), while GQA's is
    one packed QKV GEMM (``attn_ag@qkv``).  ``tuning.plans.seam_of`` maps a
    cell key back to the model seam; ``autotune_model`` tunes every cell
    and resolves the seam-level plan from its DOMINANT (largest-FLOPs)
    cell.

    ``decode_batch`` defaults to the serving runtime's ``ServeConfig.
    max_batch`` (the server's decode jit batch); pass the actual
    ``--max-batch`` when tuning for a differently-sized deployment."""
    from repro.parallel.sharding import pad_ff, pad_vocab
    if decode_batch is None:
        decode_batch = serving_decode_batch()
    tp = par.tp
    d = cfg.d_model
    ffp = pad_ff(cfg.d_ff, tp)
    shapes: Dict[str, Tuple[str, int, int, int]] = {
        "mlp_ag": ("ag", tokens_per_dp,
                   ffp * (2 if getattr(par, "fuse_w13", False) else 1), d),
        "mlp_rs": ("rs", tokens_per_dp, d, ffp),
        "head_ag": ("ag", tokens_per_dp, pad_vocab(cfg.vocab_size, tp), d),
        "decode_ar": ("ar", decode_batch, d, ffp),
    }
    if cfg.mla is not None:
        from repro.parallel.sharding import pad_heads
        mla = cfg.mla
        h_pad = pad_heads(cfg.num_heads, tp)
        # the query up-projection reads the compressed query, or the
        # hidden state itself where the query is not compressed
        shapes["attn_ag@q_up"] = (
            "ag", tokens_per_dp,
            h_pad * (mla.qk_nope_head_dim + mla.qk_rope_head_dim),
            d if mla.q_lora_rank is None else mla.q_lora_rank)
        shapes["attn_ag@kv_up"] = (
            "ag", tokens_per_dp,
            h_pad * (mla.qk_nope_head_dim + mla.v_head_dim),
            mla.kv_lora_rank)
        shapes["attn_rs"] = ("rs", tokens_per_dp, d, h_pad * mla.v_head_dim)
    elif cfg.num_heads:
        from repro.models.attention import AttnDims
        dims = AttnDims.of(cfg, tp)
        shapes["attn_ag@qkv"] = (
            "ag", tokens_per_dp,
            (dims.h_pad + 2 * dims.hkv_pad) * dims.dh, d)
        shapes["attn_rs"] = ("rs", tokens_per_dp, d, dims.h_pad * dims.dh)
    if cfg.moe is not None:
        # EP exchange seam: m = routed rows (tokens x top_k), k = d_model
        # (the a2a payload width), n = the per-expert FFN width
        shapes["moe_a2a"] = ("a2a", tokens_per_dp * cfg.moe.top_k,
                             cfg.moe.expert_ffn, d)
    return shapes


def sweep_model_layout(cfg, par, *, tokens_per_dp: int = 2048,
                       dtype_bytes: int = 2) -> Dict:
    """Joint residual-layout sweep (the ``scatter_axis`` knob): per layout,
    sum the analytic per-seam OverallTime over the residual-stream seam
    cells and the per-layer resident activation bytes.

    The layout CANNOT be tuned seam-by-seam — a lone hidden-AG seam always
    "wins" (it has no collective) while its paired RS seam silently absorbs
    the full AllReduce, so only the layer-pair totals are comparable.
    Accounting covers the PAIRED per-layer seams (mlp_ag/mlp_rs,
    attn_ag/attn_rs); head_ag is stamped with the winner but excluded from
    the totals (its volume dual is the embed seam's scatter, outside this
    table).  The comm volume is layout-invariant by construction (AG+RS
    over seq == one ring AllReduce); the decider is overlap quality vs
    activation residency, so ties (and near-ties) go to "seq" — 1/tp the
    resident activation between seams."""
    from repro.tuning.plans import seam_of
    layer_seams = ("mlp_ag", "mlp_rs", "attn_ag", "attn_rs")
    shapes = model_seam_shapes(cfg, par, tokens_per_dp)
    out: Dict[str, Dict] = {}
    for axis in ("seq", "hidden"):
        total_s, act, vol = 0.0, 0.0, 0.0
        for key, (kind, m, n, k) in shapes.items():
            if seam_of(key) not in layer_seams:
                continue
            # each layout is scored on its best honest lossless transport
            # per seam (monolithic vs overlapped ring).  Note hidden's RS
            # always resolves to the monolithic ring AllReduce: the
            # chunked-AR transport moves chunks x the bytes (see
            # ect.model_overlap), and its AG side has no collective at all.
            ests = [ect.model_overlap(kind, m, n, k, par.tp, mode,
                                      dtype_bytes, scatter_axis=axis)
                    for mode in ("xla", "decomposed")]
            est = min(ests, key=lambda e: e["overall"])
            total_s += est["overall"]
            act += est["act_bytes"]
            vol += est["comm_bytes"]
        out[axis] = {"overall_s": total_s, "act_bytes": act,
                     "comm_bytes": vol}
    # near-ties (within 2%) resolve to seq: same comm volume, 1/tp residency
    seq_s, hid_s = out["seq"]["overall_s"], out["hidden"]["overall_s"]
    out["winner"] = "seq" if seq_s <= hid_s * 1.02 else "hidden"
    out["residency_ratio"] = (out["seq"]["act_bytes"]
                              / max(out["hidden"]["act_bytes"], 1.0))
    return out


def autotune_model(cfg, par, *, tokens_per_dp: int = 2048,
                   decode_batch: Optional[int] = None, measure="auto",
                   registry=None, save_path: Optional[str] = None,
                   allow_flux: bool = True, allow_q8: bool = False,
                   wire_dtypes: Optional[Sequence[Optional[str]]] = None,
                   max_logit_rmse: Optional[float] = None,
                   sweep_scatter_axis: bool = True) -> PlanSet:
    """Tune every seam of a model and return the resulting PlanSet.

    Attention seams with several GEMM shape cells (MLA q/kv up-projections)
    are tuned PER CELL (``"attn_ag@q_up"`` ...); the seam-level plan model
    code resolves is the dominant (largest-FLOPs) cell's winner, and every
    cell plan stays resolvable under its qualified key.

    ``sweep_scatter_axis`` additionally runs the joint residual-layout
    sweep (``sweep_model_layout``) and stamps the winning ``scatter_axis``
    onto the whole PlanSet — layout is one coherent model-level decision,
    never a per-seam one.

    ``registry`` (a ``cache.PlanRegistry``) short-circuits seams it already
    holds and records fresh results; ``save_path`` persists it afterwards.
    Quantized wires are lossy and therefore an explicit opt-in for
    whole-model plans: pass ``wire_dtypes`` (e.g. ``autotune.
    WIRE_DTYPE_SWEEP``) to sweep them, ideally paired with
    ``max_logit_rmse`` so the per-seam error budget gates the winners.
    ``allow_q8`` is the deprecated spelling of ``wire_dtypes=(None,
    "int8")`` and still works.
    """
    from repro.tuning.plans import seam_of
    if par.tp <= 1:
        return PlanSet.uniform(par.overlap_mode, par.comm_chunks)
    # FusedOp shape of each seam: the gated FFN runs a two-weight silu-gate
    # op off one gather (w13-packed: one weight, split-gate — still an
    # epilogue); QKV projections fuse the bias when the arch has one.
    fused_shape: Dict[str, Dict] = {
        "mlp_ag": {"n_weights": 1 if getattr(par, "fuse_w13", False) else 2,
                   "epilogue": True},
        "attn_ag": {"epilogue": bool(getattr(cfg, "qkv_bias", False))},
        "moe_a2a": {"n_weights": 3, "epilogue": True},
    }
    # the layout decision comes FIRST: every seam is tuned UNDER the
    # winning scatter_axis, so the recorded profile persists the layout
    # (a post-save stamp would leave "auto" loads on the wrong layout)
    scatter_axis = "seq"
    if sweep_scatter_axis:
        scatter_axis = sweep_model_layout(
            cfg, par, tokens_per_dp=tokens_per_dp)["winner"]
    seams: Dict[str, SeamPlan] = {}
    flops: Dict[str, Tuple[int, str]] = {}    # seam -> (dominant flops, cell)
    for cell_key, (kind, m, n, k) in model_seam_shapes(
            cfg, par, tokens_per_dp, decode_batch).items():
        seam_name = seam_of(cell_key)
        cached = registry.lookup(cell_key, m, n, k) if registry else None
        if cached is not None:
            seams[cell_key] = cached
        else:
            res = tune_seam(kind, m, n, k, par.tp, allow_flux=allow_flux,
                            allow_q8=allow_q8, measure=measure,
                            wire_dtypes=wire_dtypes,
                            max_logit_rmse=max_logit_rmse,
                            seam=cell_key, scatter_axis=scatter_axis,
                            **fused_shape.get(seam_name, {}))
            seams[cell_key] = res.plan
            if registry is not None:
                registry.record(cell_key, kind, m, n, k, res.plan)
        # seam-level resolution: the dominant cell's plan
        cell_flops = 2 * m * n * k
        if cell_key != seam_name and (seam_name not in flops
                                      or cell_flops > flops[seam_name][0]):
            flops[seam_name] = (cell_flops, cell_key)
    for seam_name, (_, cell_key) in flops.items():
        seams[seam_name] = seams[cell_key]
    if registry is not None:
        if sweep_scatter_axis:
            # cached entries may predate this run's layout decision: stamp
            # the WHOLE registry so the persisted profile stays coherent
            # (a mixed-layout profile raises at load)
            registry.stamp_scatter_axis(scatter_axis)
        if save_path:
            registry.save(save_path)
    plans = PlanSet(default=SeamPlan(mode=par.overlap_mode,
                                     comm_chunks=par.comm_chunks).validate(),
                    seams=seams)
    if sweep_scatter_axis:
        # coherence stamp (covers cached entries tuned under another layout)
        plans = plans.with_scatter_axis(scatter_axis)
    return plans
