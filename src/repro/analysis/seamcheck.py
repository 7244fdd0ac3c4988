"""Seam-contract verifier: jaxpr-level invariants, checked abstractly.

The repo's TP/SP correctness story rests on three contracts that used to be
asserted only for the few mixer×layout combos individual tests happened to
trace.  This module turns them into machine-checked invariants over the
ABSTRACT trace (``jax.make_jaxpr`` with an ``axis_env`` — no devices, no
execution, runs on CPU CI) of every config's train/prefill/decode step:

1. **Collective census with ring provenance.**  Every collective transport
   ``repro.core.overlap`` emits is wrapped in a ``jax.named_scope`` whose
   name starts with ``overlap.SEAM_SCOPE_PREFIX`` ("seam").  The scope
   lands on the eqn's ``source_info.name_stack`` and survives jvp/transpose
   wrapping, scan bodies and custom_vjp backward rules — so any
   full-activation ``psum``/``all_gather``/``psum_scatter``/``ppermute``/
   ``all_to_all`` over the TP axis WITHOUT a seam scope is a standalone
   collective no seam owns: a census violation, reported with the eqn's
   shapes/provenance.

2. **Partial-cotangent completion.**  Under the repo's check_rep=False
   convention a replicated tensor's cotangent arrives as a per-rank
   PARTIAL; it must be completed by a psum exactly where a rank-exclusive
   operand consumes it (the PR 5 mamba x_proj bug class).  A dataflow taint
   walk over the vjp jaxpr verifies every ``dot_general`` contracting the
   cotangent sees a completed value (``expect_complete=True``) — or that NO
   spurious completing psum appears when the cotangent arrives full
   (``expect_complete=False``, the sequence-sharded seams, where a psum
   would double-count).

3. **Layout coherence.**  ``PlanSet.residual_layout()`` must resolve for
   the stamped layout; the sequence-sharded decomposed trace must contain
   ZERO standalone ``all_gather`` eqns (everything rides seam ppermute
   rings); the replicated-layout trace must contain ZERO ``ppermute`` eqns
   (no rings exist to ride); decode always runs the replicated layout (no
   ppermute, no reduce_scatter).

Shared walker: tests use :func:`collect_collectives` / :func:`count` so the
suite and the checker count collectives identically (the ad-hoc string
censuses this replaces disagreed on e.g. ``psum_scatter`` tracing as a
``reduce_scatter`` primitive).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.core.overlap import SEAM_SCOPE_PREFIX

# primitive names as they appear in traced jaxprs (``lax.psum_scatter``
# traces as a ``reduce_scatter`` eqn; ``pmean`` lowers to psum + div).
# ``all_to_all`` joined the census with the MoE EP exchange seam: a
# full-activation dispatch/combine without a seam scope is exactly the
# unattributed-transport class the census exists to catch.
CENSUS_PRIMS = ("psum", "all_gather", "reduce_scatter", "ppermute",
                "pmax", "pmin", "all_to_all")
ALL_COLLECTIVE_PRIMS = CENSUS_PRIMS


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective eqn found in a traced jaxpr."""
    prim: str
    axes: Tuple[str, ...]            # named mesh axes it communicates over
    shape: Tuple[int, ...]           # first array operand's shape
    dtype: str
    scope: str                       # str(eqn.source_info.name_stack)
    source: str                      # "file:line (fn)" best-effort
    trips: int = 1                   # scan trip-count multiplier

    @property
    def elems(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def seam_tagged(self) -> bool:
        return SEAM_SCOPE_PREFIX in self.scope

    def describe(self) -> str:
        tag = self.scope if self.scope else "<no scope>"
        src = f" at {self.source}" if self.source else ""
        return (f"{self.prim} over {self.axes} shape={self.shape} "
                f"dtype={self.dtype} x{self.trips} [{tag}]{src}")


def _axes_of(eqn) -> Tuple[str, ...]:
    axes = (eqn.params.get("axes") or eqn.params.get("axis_name")
            or eqn.params.get("axis"))
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    if not isinstance(axes, (tuple, list)):
        return ()
    return tuple(a for a in axes if isinstance(a, str))


def _source_of(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return ""


def _sub_jaxprs(eqn):
    """Every (Closed)Jaxpr hiding in an eqn's params (jaxpr_cost idiom)."""
    out = []
    for v in eqn.params.values():
        if hasattr(v, "eqns"):
            out.append(v)
        elif hasattr(v, "jaxpr"):
            out.append(v.jaxpr)
        elif isinstance(v, (tuple, list)):
            for b in v:
                if hasattr(b, "eqns"):
                    out.append(b)
                elif hasattr(b, "jaxpr"):
                    out.append(b.jaxpr)
    return out


def collect_collectives(jaxpr, _trips: int = 1) -> List[Collective]:
    """Recursively enumerate every collective eqn in a (Closed)Jaxpr —
    scan bodies annotated with their trip count, shard_map/pjit/custom_vjp
    sub-jaxprs walked through."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[Collective] = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            sub = eqn.params["jaxpr"]
            out.extend(collect_collectives(
                sub, _trips * int(eqn.params["length"])))
            continue
        if prim in ALL_COLLECTIVE_PRIMS:
            aval = next((v.aval for v in eqn.invars
                         if hasattr(v, "aval") and hasattr(v.aval, "shape")),
                        None)
            shape = tuple(aval.shape) if aval is not None else ()
            dtype = str(aval.dtype) if aval is not None else "?"
            out.append(Collective(
                prim=prim, axes=_axes_of(eqn), shape=shape, dtype=dtype,
                scope=str(getattr(eqn.source_info, "name_stack", "")),
                source=_source_of(eqn), trips=_trips))
            continue
        for sub in _sub_jaxprs(eqn):
            out.extend(collect_collectives(sub, _trips))
    return out


def count(jaxpr, prim: str, weighted: bool = False) -> int:
    """Number of ``prim`` collective eqns in the trace (``weighted=True``
    multiplies scan bodies by their trip count)."""
    return sum((c.trips if weighted else 1)
               for c in collect_collectives(jaxpr) if c.prim == prim)


def collective_counts(jaxpr, weighted: bool = False) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for c in collect_collectives(jaxpr):
        out[c.prim] = out.get(c.prim, 0) + (c.trips if weighted else 1)
    return out


# ---------------------------------------------------------------------------
# Contract 1: collective census with ring provenance
# ---------------------------------------------------------------------------
def census_errors(colls: Sequence[Collective], tp_axis: str = "model",
                  min_elems: int = 0) -> List[str]:
    """Every census collective over the TP axis at full-activation scale
    must carry a seam scope.  ``min_elems`` is the full-activation
    threshold (the residual shard's element count) — the tiny reductions
    (xent partition function, loss means, vocab-argmax candidates) ride
    under it by orders of magnitude."""
    errs = []
    for c in colls:
        if c.prim not in CENSUS_PRIMS:
            continue
        if tp_axis not in c.axes:
            continue                      # dp/pod traffic: not a TP seam
        if c.seam_tagged:
            continue
        if c.elems < min_elems:
            continue
        errs.append("unattributed full-activation collective (no seam "
                    f"scope): {c.describe()}")
    return errs


# ---------------------------------------------------------------------------
# Contract 2: partial-cotangent completion (dataflow taint walk)
# ---------------------------------------------------------------------------
def _taint_walk(jaxpr, tainted: set, completed: set, tp_axis: str,
                events: List[Tuple[str, object]]):
    """Propagate cotangent taint through one jaxpr's eqns (topological
    order).  ``tainted``/``completed`` are Var sets mutated in place;
    ``events`` collects ("raw_dot"|"psum", eqn) records."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        in_vars = [v for v in eqn.invars if hasattr(v, "aval")
                   and not isinstance(v, compat.Literal)]
        t_in = [v for v in in_vars if v in tainted]
        if not t_in:
            # sub-jaxprs with no tainted inputs can still not introduce
            # taint (taint only enters via invars here)
            continue
        raw_in = [v for v in t_in if v not in completed]

        if prim == "psum" and tp_axis in _axes_of(eqn):
            events.append(("psum", eqn))
            for o in eqn.outvars:
                tainted.add(o)
                completed.add(o)
            continue
        if prim == "dot_general":
            if raw_in:
                events.append(("raw_dot", eqn))
            for o in eqn.outvars:
                tainted.add(o)
                if not raw_in:
                    completed.add(o)
            continue

        subs = _sub_jaxprs(eqn)
        if subs and prim not in ALL_COLLECTIVE_PRIMS:
            mapped = False
            for sub in subs:
                inner = getattr(sub, "jaxpr", sub)
                if len(inner.invars) == len(eqn.invars):
                    # 1:1 call convention (pjit/closed_call/custom_*/scan)
                    for ov, iv in zip(eqn.invars, inner.invars):
                        if hasattr(ov, "aval") and ov in tainted:
                            tainted.add(iv)
                            if ov in completed:
                                completed.add(iv)
                    _taint_walk(inner, tainted, completed, tp_axis, events)
                    if len(inner.outvars) == len(eqn.outvars):
                        for ov, iv in zip(eqn.outvars, inner.outvars):
                            iv = getattr(iv, "val", iv)
                            if iv in tainted:
                                tainted.add(ov)
                                if iv in completed:
                                    completed.add(ov)
                        mapped = True
            if mapped:
                continue
            # unmappable control flow: conservative propagation
            for o in eqn.outvars:
                tainted.add(o)
                if not raw_in:
                    completed.add(o)
            continue

        # default propagation: taint flows; completion survives only if
        # every tainted input was completed
        for o in eqn.outvars:
            tainted.add(o)
            if not raw_in:
                completed.add(o)


def check_cotangent_completion(fn, args: Sequence, ct, *,
                               tp_axis: str = "model",
                               axis_env: Sequence[Tuple[str, int]] = (
                                   ("model", 4),),
                               expect_complete: bool = True,
                               label: str = "") -> List[str]:
    """Trace ``vjp(fn)(ct)`` abstractly and verify the completion contract.

    ``expect_complete=True``: the output is REPLICATED, so its cotangent is
    a per-rank partial — every ``dot_general`` consuming it must be
    dominated by a ``psum`` over ``tp_axis`` (a raw contraction is the PR 5
    bug class).  ``expect_complete=False``: the output is rank-exclusive,
    the cotangent arrives full — any completing psum on its path would
    double-count and is reported instead.
    """
    def bwd(ct_, *args_):
        _, vjp = jax.vjp(fn, *args_)
        return vjp(ct_)

    closed = jax.make_jaxpr(bwd, axis_env=list(axis_env))(ct, *args)
    n_ct = len(jax.tree.leaves(ct))
    seeds = set(closed.jaxpr.invars[:n_ct])
    tainted, completed = set(seeds), set()
    events: List[Tuple[str, object]] = []
    _taint_walk(closed.jaxpr, tainted, completed, tp_axis, events)

    where = f" [{label}]" if label else ""
    errs = []
    dots = [e for k, e in events if k == "raw_dot"]
    psums = [e for k, e in events if k == "psum"]
    if expect_complete:
        for eqn in dots:
            errs.append(
                "raw (uncompleted) cotangent contraction — partial "
                f"cotangent consumed by dot_general without a dominating "
                f"psum over {tp_axis!r}{where}: {_source_of(eqn)}")
        if not dots and not psums and not any(
                k == "raw_dot" or k == "psum" for k, _ in events):
            # nothing on the cotangent path touched a dot or psum at all:
            # the trace did not exercise the backward as expected
            errs.append(f"cotangent check traced no contraction{where} — "
                        "backward not exercised")
    else:
        for eqn in psums:
            errs.append(
                "spurious cotangent completion — full (rank-exclusive) "
                f"cotangent psum'd over {tp_axis!r} (double-counts) "
                f"{where}: {_source_of(eqn)}")
    return errs


def fusedop_cotangent_errors(tp: int = 4, modes: Sequence[str] = (
        "decomposed", "xla"),
        wire_dtypes: Sequence[Optional[str]] = (None, "int8")) -> List[str]:
    """The completion matrix over every FusedOp (kind, layout): replicated
    outputs (ar, rs/hidden) must complete their cotangent; rank-exclusive
    outputs (seq seams, ag/hidden's partial dx, the a2a exchange's routed
    rows and local-expert weights) must not.  The matrix sweeps
    ``wire_dtypes`` too — quantization is forward-wire-only, so a
    quantized transport must keep the SAME completion contract as its fp
    twin (a wire that altered the cotangent path is exactly the bug this
    matrix exists to catch)."""
    from repro.core.overlap import Epilogue, FusedOp

    b, s, d, f = 2, 16, 16, 32
    sl = s // tp
    cases = [
        # (kind, scatter_axis, x_shape, w_shape, expect_complete)
        ("ag", "seq", (b, sl, d), (d, f), False),
        ("ag", "hidden", (b, s, d), (d, f), False),
        ("rs", "seq", (b, s, f // tp), (f // tp, d), False),
        ("rs", "hidden", (b, s, f // tp), (f // tp, d), True),
        ("ar", "hidden", (b, 1, f // tp), (f // tp, d), True),
    ]
    env = [("model", tp)]
    errs: List[str] = []
    for mode in modes:
        for wire in wire_dtypes:
            for kind, lay, xs, wshape, expect in cases:
                op = FusedOp(kind=kind, axis="model", mode=mode,
                             scatter_axis=lay, wire_dtype=wire)
                x = jax.ShapeDtypeStruct(xs, jnp.float32)
                w = jax.ShapeDtypeStruct(wshape, jnp.float32)

                def fn(x_, w_, op=op):
                    return op(x_, w_)

                ct_aval = jax.make_jaxpr(fn, axis_env=env)(x, w).out_avals[0]
                ct = jax.ShapeDtypeStruct(ct_aval.shape, ct_aval.dtype)
                errs.extend(check_cotangent_completion(
                    fn, (x, w), ct, tp_axis="model", axis_env=env,
                    expect_complete=expect,
                    label=(f"FusedOp kind={kind} layout={lay} mode={mode}"
                           f" wire={wire}")))
    # EP exchange op: dispatch a2a + batched expert SwiGLU + combine a2a in
    # one seam.  Its outputs are rank-exclusive on every path — dx is this
    # rank's own routed rows, and dw is the LOCAL experts' full gradient
    # (every EP peer's token contribution arrives through the backward
    # exchange, never through a completing psum) — so any psum over the TP
    # axis on the cotangent path double-counts.
    e_loc, cap = 2, 4
    for mode in modes:
        for wire in wire_dtypes:
            op = FusedOp(kind="a2a", axis=("model",), mode=mode,
                         epilogue=Epilogue(activation="silu", gate="pair"),
                         n_weights=3, wire_dtype=wire)
            x = jax.ShapeDtypeStruct((tp, e_loc, cap, d), jnp.float32)
            w1 = jax.ShapeDtypeStruct((e_loc, d, f), jnp.float32)
            w3 = jax.ShapeDtypeStruct((e_loc, d, f), jnp.float32)
            w2 = jax.ShapeDtypeStruct((e_loc, f, d), jnp.float32)

            def a2a_fn(x_, a_, b_, c_, op=op):
                return op(x_, a_, b_, c_)

            ct_aval = jax.make_jaxpr(a2a_fn, axis_env=env)(
                x, w1, w3, w2).out_avals[0]
            ct = jax.ShapeDtypeStruct(ct_aval.shape, ct_aval.dtype)
            errs.extend(check_cotangent_completion(
                a2a_fn, (x, w1, w3, w2), ct, tp_axis="model", axis_env=env,
                expect_complete=False,
                label=f"FusedOp kind=a2a mode={mode} wire={wire}"))
    return errs


# ---------------------------------------------------------------------------
# Abstract tracing harness (axis_env: no mesh, no devices, no execution)
# ---------------------------------------------------------------------------
def _local_sds(sds_tree, spec_tree, sizes: Dict[str, int]):
    """Per-device ShapeDtypeStructs from global shapes + PartitionSpecs."""
    def one(leaf, spec):
        shape = list(leaf.shape)
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                if sizes.get(a, 1) and shape[i] % sizes[a] == 0:
                    shape[i] //= sizes[a]
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    from jax.sharding import PartitionSpec as P
    return jax.tree.map(one, sds_tree, spec_tree,
                        is_leaf=lambda x: isinstance(
                            x, (jax.ShapeDtypeStruct, P)))


def _batch_sds(cfg, b: int, s: int, tp: int, seq_sharded: bool):
    if getattr(cfg, "frontend", None):
        s_loc = s // tp if seq_sharded else s
        return {"embeds": jax.ShapeDtypeStruct((b, s_loc, cfg.d_model),
                                               jnp.bfloat16),
                "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}


def _ctx_for(cfg, par, plans):
    from repro.models import model as M
    from repro.parallel.sharding import TPContext
    return TPContext(axis="model", dp_axes=("data",),
                     ep_axes=M._ep_axes(cfg, par), plans=plans)


def _local_params(cfg, par, sizes):
    from repro.models import model as M
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: M.init_model(key, cfg, par))
    specs = M.param_specs(cfg, par, params)
    return _local_sds(params, specs, sizes)


def trace_train(cfg, par, plans, tp: int = 4, b: int = 2, s: int = 64):
    """Abstract fwd+bwd train-step jaxpr (value_and_grad of forward_loss)."""
    from repro.models import model as M
    sizes = {"data": 1, "model": tp}
    params_l = _local_params(cfg, par, sizes)
    seq_sharded = plans.residual_layout() == "seq"
    batch = _batch_sds(cfg, b, s, tp, seq_sharded)
    ctx = _ctx_for(cfg, par, plans)

    def step(p, bt):
        return jax.value_and_grad(
            lambda pp: M.forward_loss(pp, bt, ctx, cfg, par))(p)

    return jax.make_jaxpr(step, axis_env=[("data", 1), ("model", tp)])(
        params_l, batch)


def trace_prefill(cfg, par, plans, tp: int = 4, b: int = 2, s: int = 64):
    from repro.models import serve as S
    sizes = {"data": 1, "model": tp}
    params_l = _local_params(cfg, par, sizes)
    seq_sharded = plans.residual_layout() == "seq"
    batch = _batch_sds(cfg, b, s, tp, seq_sharded)
    batch.pop("labels")
    ctx = _ctx_for(cfg, par, plans)

    def step(p, bt):
        return S.prefill_step(p, bt, ctx, cfg, par)

    return jax.make_jaxpr(step, axis_env=[("data", 1), ("model", tp)])(
        params_l, batch)


def trace_decode(cfg, par, plans, tp: int = 4, b: int = 2, s_max: int = 64,
                 paged: bool = False):
    """``paged=True`` traces block-table decode (``decode_step`` with
    ``block_tables`` over ``paged_cache_specs`` pools) — same seam
    contract as dense decode: kind="ar" only, replicated layout."""
    from repro.models import serve as S
    sizes = {"data": 1, "model": tp}
    params_l = _local_params(cfg, par, sizes)
    if paged:
        bs = 8
        pages = s_max // bs
        csds, cspec = S.paged_cache_specs(cfg, par, b * pages + 1, bs, b)
        bt = jax.ShapeDtypeStruct((b, pages), jnp.int32)
    else:
        csds, cspec = S.cache_specs(cfg, par, b, s_max, ("data",))
        bt = None
    caches_l = _local_sds(csds, cspec, sizes)
    tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    ctx = _ctx_for(cfg, par, plans)

    def step(p, c, t, po, bt_=None):
        return S.decode_step(p, c, t, po, ctx, cfg, par, block_tables=bt_)

    if paged:
        return jax.make_jaxpr(step, axis_env=[("data", 1), ("model", tp)])(
            params_l, caches_l, tokens, pos, bt)
    return jax.make_jaxpr(step, axis_env=[("data", 1), ("model", tp)])(
        params_l, caches_l, tokens, pos)


def trace_prefill_chunk(cfg, par, plans, tp: int = 4, b: int = 2,
                        s_max: int = 64, chunk: int = 16):
    """Chunked-prefill census lane: one fixed-shape ``[1, chunk]`` admission
    chunk through the block-table paged pools (``serve.prefill_chunk_step``
    with traced int32 slot/off/chunk_len scalars — the single jit program
    the serving runtime dispatches O(n/C) times per prompt).

    Chunked admission ALWAYS runs the replicated activation layout (like
    decode): a bounded C-row chunk has no sequence-parallel residency to
    win, so its collectives must be kind="ar" seams only — no ppermute
    rings, no sequence reduce_scatter."""
    from repro.models import serve as S
    sizes = {"data": 1, "model": tp}
    params_l = _local_params(cfg, par, sizes)
    bs = 8
    pages = s_max // bs
    csds, cspec = S.paged_cache_specs(cfg, par, b * pages + 1, bs, b)
    caches_l = _local_sds(csds, cspec, sizes)
    tokens = jax.ShapeDtypeStruct((1, chunk), jnp.int32)
    bt = jax.ShapeDtypeStruct((1, pages), jnp.int32)   # ONE slot's table row
    scal = jax.ShapeDtypeStruct((), jnp.int32)
    ctx = _ctx_for(cfg, par, plans)

    def step(p, c, t, bt_, slot, off, clen):
        return S.prefill_chunk_step(p, c, t, bt_, slot, off, clen,
                                    ctx, cfg, par)

    return jax.make_jaxpr(step, axis_env=[("data", 1), ("model", tp)])(
        params_l, caches_l, tokens, bt, scal, scal, scal)


# ---------------------------------------------------------------------------
# Contract 3: layout coherence
# ---------------------------------------------------------------------------
def layout_errors(train_colls: Sequence[Collective],
                  decode_colls: Optional[Sequence[Collective]],
                  layout: str, mode: str, min_elems: int = 0) -> List[str]:
    """Full-activation transport only — tiny cross-rank exchanges (the
    token-shift boundary, vocab-argmax candidates) are seam-tagged and
    orders of magnitude under ``min_elems``."""
    big = [c for c in train_colls if c.elems >= min_elems]
    errs = []
    # "seam_wire"-scoped hops are the quantized transports: a quantized
    # all-reduce is SPELLED as ppermute rings even under the replicated
    # layout (psum cannot carry the per-block scales), so the no-ring
    # layout rules exempt them — they remain seam-tagged and censused.
    wire_hop = lambda c: "seam_wire" in c.scope  # noqa: E731
    if layout == "hidden":
        pp = [c for c in big if c.prim == "ppermute" and not wire_hop(c)]
        for c in pp:
            errs.append("replicated layout must not ride ppermute rings "
                        f"(nothing is sequence-sharded): {c.describe()}")
    if layout == "seq" and mode.startswith("decomposed"):
        ag = [c for c in big if c.prim == "all_gather"]
        for c in ag:
            errs.append("sequence-sharded decomposed trace contains a "
                        "standalone all_gather (must ride a seam ppermute "
                        f"ring): {c.describe()}")
        rep = [c for c in train_colls
               if "seam_replicated_sum" in c.scope
               or "seam_embed_ar" in c.scope]
        for c in rep:
            errs.append("replicated-combine collective under the "
                        f"sequence-sharded layout: {c.describe()}")
    if decode_colls is not None:
        for c in decode_colls:
            if c.prim == "ppermute" and not wire_hop(c):
                errs.append("decode must run the replicated layout — no "
                            f"ppermute belongs in it: {c.describe()}")
            if c.prim == "reduce_scatter":
                errs.append("decode must not sequence-scatter (one-token "
                            f"activations stay replicated): {c.describe()}")
    return errs


# ---------------------------------------------------------------------------
# Top-level: every config x both layouts
# ---------------------------------------------------------------------------
def discover_configs() -> List[str]:
    """Every module in src/repro/configs/ that defines ``CONFIG``."""
    import importlib
    import pkgutil

    from repro import configs as cpkg
    names = []
    for info in pkgutil.iter_modules(cpkg.__path__):
        mod = importlib.import_module(f"repro.configs.{info.name}")
        if hasattr(mod, "CONFIG"):
            names.append(info.name)
    return sorted(names)


def check_config(name: str, layout: str, mode: str = "decomposed",
                 tp: int = 4, b: int = 2, s: int = 64,
                 wire_dtype: Optional[str] = None,
                 log=None) -> List[str]:
    """All three contract families for one config x layout (smoke shapes —
    the invariants are structural, not size-dependent).  ``wire_dtype``
    stamps a quantized wire onto every plan: the census then runs over the
    quantized transports, which must stay seam-tagged and layout-correct
    exactly like their fp twins."""
    import dataclasses as _dc

    from repro.configs.base import ParallelConfig, get_smoke_config
    from repro.tuning.plans import PlanSet

    cfg = get_smoke_config(name)
    par = ParallelConfig(tp=tp, dp=1, overlap_mode=mode, scatter_axis=layout,
                         wire_dtype=wire_dtype)
    plans = PlanSet.uniform(mode).with_scatter_axis(layout)
    if wire_dtype is not None:
        plans = plans.with_wire_dtype(wire_dtype)
    errs: List[str] = []
    try:
        resolved = plans.residual_layout()
    except ValueError as e:
        return [f"{name}/{layout}: incoherent PlanSet layout: {e}"]
    if resolved != layout:
        errs.append(f"{name}/{layout}: residual_layout() resolved "
                    f"{resolved!r}")

    s_loc = s // tp
    threshold = b * s_loc * cfg.d_model      # the residual shard
    prefix = f"{name}/{layout}"
    if wire_dtype is not None:
        prefix += f"/wire-{wire_dtype}"

    train = trace_train(cfg, par, plans, tp=tp, b=b, s=s)
    tc = collect_collectives(train)
    errs += [f"{prefix}/train: {e}"
             for e in census_errors(tc, "model", threshold)]

    prefill = trace_prefill(cfg, par, plans, tp=tp, b=b, s=s)
    pc = collect_collectives(prefill)
    errs += [f"{prefix}/prefill: {e}"
             for e in census_errors(pc, "model", threshold)]

    dc = None
    if layout == "hidden":
        # decode ALWAYS forces the replicated layout — trace it once, on
        # the hidden pass (the layout knob cannot change its jaxpr).
        # Both lanes: dense per-slot caches AND block-table paged pools
        # (the serving runtime runs the paged lane exclusively).
        par_d = _dc.replace(par, scatter_axis="hidden")
        decode = trace_decode(cfg, par_d, plans, tp=tp, b=b, s_max=s)
        dc = collect_collectives(decode)
        errs += [f"{prefix}/decode: {e}"
                 for e in census_errors(dc, "model", threshold)]
        paged = trace_decode(cfg, par_d, plans, tp=tp, b=b, s_max=s,
                             paged=True)
        pgc = collect_collectives(paged)
        errs += [f"{prefix}/decode-paged: {e}"
                 for e in census_errors(pgc, "model", threshold)]
        # chunked-prefill admission rides the SAME replicated-layout
        # contract as decode: census over one [1, chunk] chunk dispatch
        # (threshold = the full chunk activation), then the decode-side
        # layout rules (no ppermute, no sequence reduce_scatter)
        chunk = max(s // 4, 1)
        ckc = collect_collectives(trace_prefill_chunk(
            cfg, par_d, plans, tp=tp, b=b, s_max=s, chunk=chunk))
        errs += [f"{prefix}/prefill-chunk: {e}"
                 for e in census_errors(ckc, "model",
                                        chunk * cfg.d_model)]
        dc = list(dc) + list(pgc) + list(ckc)

    errs += [f"{prefix}: {e}"
             for e in layout_errors(tc, dc, layout, mode, threshold)]
    errs += [f"{prefix}/prefill: {e}"
             for e in layout_errors(pc, None, layout, mode, threshold)]
    if log:
        log(f"  {prefix}: {len(tc)} train / {len(pc)} prefill"
            + (f" / {len(dc)} decode" if dc is not None else "")
            + " collectives — "
            + ("OK" if not errs else f"{len(errs)} violation(s)"))
    return errs


def run_seam_checks(config_names: Optional[Sequence[str]] = None,
                    layouts: Sequence[str] = ("seq", "hidden"),
                    mode: str = "decomposed", tp: int = 4,
                    log=None) -> List[str]:
    """The full seam-contract pass: every config x every layout, plus the
    FusedOp cotangent-completion matrix (config-independent)."""
    names = list(config_names) if config_names else discover_configs()
    errs: List[str] = []
    for name in names:
        for layout in layouts:
            try:
                errs.extend(check_config(name, layout, mode=mode, tp=tp,
                                         log=log))
            except Exception as e:       # a config that cannot trace IS
                errs.append(             # a finding, not a crash
                    f"{name}/{layout}: trace failed: "
                    f"{type(e).__name__}: {e}")
    # quantized-wire census spot-check: one representative config, BOTH
    # layouts, int8 wire — the quantized transports must stay seam-tagged
    # and layout-correct (structural contracts are wire-invariant, so one
    # config suffices; the full matrix above stays fp)
    for layout in layouts:
        try:
            errs.extend(check_config(names[0], layout, mode=mode, tp=tp,
                                     wire_dtype="int8", log=log))
        except Exception as e:
            errs.append(f"{names[0]}/{layout}/wire-int8: trace failed: "
                        f"{type(e).__name__}: {e}")
    cot = fusedop_cotangent_errors(tp=tp)
    if log:
        log(f"  cotangent-completion matrix: "
            + ("OK" if not cot else f"{len(cot)} violation(s)"))
    errs.extend(cot)
    return errs
