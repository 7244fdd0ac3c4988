"""Static DMA-schedule, race, and tile-budget verifier for the flux kernels.

The FLUX thesis moves the ``DataTransfer -> SetSignal -> WaitSignal``
protocol (paper Algorithms 2/3) *inside* fused Pallas kernels — exactly the
code the jaxpr-level seam checks cannot see: ``make_async_remote_copy``
rings, semaphore waits and the output-tile swizzle live in the kernel body,
and their invariants were, until this module, comments.

``kernelcheck`` executes each registered kernel's grid program ABSTRACTLY
(per grid cell, per logical rank — no devices, no Mosaic, no numerics): the
real wrapper (``ag_gemm`` / ``gemm_rs`` / ...) is called under a patched
``compat.pallas_call`` that captures the kernel body, grid, specs and
scratch shapes from the genuine call site (zero drift), then the body runs
once per (rank, grid cell) against shim Refs with ``pl.program_id`` /
``pl.when`` / ``lax.axis_index`` / ``compat.make_async_*copy`` replaced by
concrete recorders.  The per-rank event streams are replayed by a scheduler
that matches DMA sends to semaphore waits and builds a happens-before order
(vector clocks), giving five machine-checked contract classes:

1. **semaphore balance** — every remote-copy send/recv signal is matched by
   a wait and all semaphores balance by kernel exit (a stuck wait, an
   undrained send, or an unconsumed arrival is reported with its grid cell).
2. **slot race freedom** — an ``a_agg``/work-buffer slot landing from a DMA is
   never read or written without a happens-before edge through the arriving
   step's recv-semaphore wait, and no slot is written by two unordered DMAs
   (flagged with step/slot provenance).
3. **ring arithmetic** — the remote-copy neighbor and the shard index used
   at step ``s`` must match the decomposed-ring reference schedule, derived
   LIVE from ``core/overlap.py``'s ``_ring_perm`` (the same permutation the
   seam-layer ppermute rings ride) for both ring directions.
4. **tile coverage** — the output-tile swizzle writes every element of the
   output exactly once across the full grid, per rank.
5. **tile budget** — a static VMEM/SMEM footprint model per
   ``(bm, bk, bn, dtype, epilogue)`` rejects infeasible tilings;
   :func:`flux_tile_footprint` is the closed form ``tuning/autotune.py``
   uses to prune flux block candidates before any timed sweep.

Values never matter (backing arrays are zeros; only shapes, indices and
event order are checked), so the trace is cheap: smoke-config shape cells
keep every grid under a few hundred cells.

Registering a new kernel: add a :class:`KernelCase` builder via
:func:`register` (a zero-arg callable that invokes the real wrapper with a
config-derived shape cell; declare ``kind="ag"``/``"rs"`` + ``n_dev`` +
``reverse`` for ring kernels so the ring-arithmetic contract applies).
Escape hatch: there is none on purpose — a kernel that cannot satisfy the
five contracts under this model needs a model extension reviewed here, not
a per-kernel waiver.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

#: per-core VMEM on current TPUs (the Pallas guide's ~16 MB figure); the
#: budget model rejects tilings whose static footprint exceeds it.
VMEM_LIMIT_BYTES = 16 * 2 ** 20
#: SMEM holds scalars/descriptors only — a kernel wanting more than this in
#: scalar memory is structurally wrong.
SMEM_LIMIT_BYTES = 16 * 2 ** 10
#: hard per-rank cell cap: shape cells must stay smoke-sized (the contract
#: classes are structural, not size-dependent — same rule as seamcheck).
MAX_GRID_CELLS = 4096

_AXIS = "model"
_TP = 4


# ---------------------------------------------------------------------------
# tile-budget closed form (the autotune pruning model)
# ---------------------------------------------------------------------------
def flux_tile_footprint(kind: str, bm: int, bk: int, bn: int, *,
                        dtype_bytes: int = 2,
                        out_bytes: Optional[int] = None,
                        partial_bytes: Optional[int] = None,
                        has_bias: bool = False) -> int:
    """Static VMEM bytes of one flux kernel instance for blocks (bm,bk,bn).

    Mirrors the ``scratch_shapes`` of ``kernels/ag_gemm.py`` /
    ``kernels/gemm_rs.py`` exactly (the kernelcheck trace cross-checks the
    two stay in sync): fp32 accumulator + A/B input tiles + cast/stage
    buffers + the optional bias tile.  The HBM work buffers (``a_agg``/
    ``ws``, declared as extra outputs) are not VMEM and are excluded.
    """
    assert kind in ("ag", "rs"), kind
    ob = out_bytes or dtype_bytes
    acc = 4 * bm * bn                           # fp32 accumulator
    a = dtype_bytes * bm * bk                   # A tile
    b = dtype_bytes * bk * bn                   # B tile
    bias = dtype_bytes * bn if has_bias else 0
    if kind == "ag":
        return acc + a + b + ob * bm * bn + bias          # + output cast
    pb = partial_bytes or ob
    # rs: partial stage + output cast buffers
    return acc + a + b + pb * bm * bn + ob * bm * bn + bias


def tile_budget_ok(kind: str, blocks: Tuple[int, int, int], *,
                   dtype_bytes: int = 2, out_bytes: Optional[int] = None,
                   partial_bytes: Optional[int] = None,
                   has_bias: bool = False,
                   limit: int = VMEM_LIMIT_BYTES) -> bool:
    """True iff the flux tiling's static VMEM footprint fits ``limit``.

    This is the predicate ``tuning/autotune.py`` applies to every flux
    ``blocks`` candidate BEFORE pricing or timing it.
    """
    bm, bk, bn = blocks
    return flux_tile_footprint(kind, bm, bk, bn, dtype_bytes=dtype_bytes,
                               out_bytes=out_bytes,
                               partial_bytes=partial_bytes,
                               has_bias=has_bias) <= limit


# ---------------------------------------------------------------------------
# ring reference schedule — derived live from core/overlap.py
# ---------------------------------------------------------------------------
def _overlap_ring_perm(n_dev: int, reverse: bool) -> List[Tuple[int, int]]:
    """The (src, dst) ppermute pairs of the seam layer's decomposed ring,
    obtained by probing ``overlap._ring_perm`` under an abstract axis env —
    the kernels are checked against the SAME schedule the jaxpr seams ride,
    so the two ring implementations cannot drift apart silently."""
    from repro.core import overlap
    got: Dict[str, List[Tuple[int, int]]] = {}

    def probe():
        got["perm"] = overlap._ring_perm(_AXIS, reverse)
        return jnp.zeros(())

    jax.make_jaxpr(probe, axis_env=[(_AXIS, n_dev)])()
    return [(int(s), int(d)) for s, d in got["perm"]]


def ring_schedules(n_dev: int, reverse: bool):
    """(nbr, ag_owner, rs_owner) reference tables for one ring direction.

    ``nbr[me]`` — the downstream neighbor every in-kernel remote copy must
    target.  ``ag_owner[me][s]`` — the shard rank ``me`` holds (and
    multiplies) at AllGather-ring step ``s``: step 0 is the local shard,
    then each hop hands the held shard downstream (paper §4.3 ring order).
    ``rs_owner[me][s]`` — the output owner whose partial rank ``me``
    computes at ReduceScatter step ``s``; the recurrence runs backwards
    from the terminal condition ``rs_owner[me][n-1] == me`` (the last step
    emits the local shard).  Both tables are pure consequences of the
    overlap.py permutation — no second copy of the ring arithmetic."""
    perm = _overlap_ring_perm(n_dev, reverse)
    nbr = {src: dst for src, dst in perm}
    ag = [[0] * n_dev for _ in range(n_dev)]
    for r in range(n_dev):
        ag[r][0] = r
    for s in range(1, n_dev):
        for src, dst in perm:
            ag[dst][s] = ag[src][s - 1]
    rs = [[0] * n_dev for _ in range(n_dev)]
    for r in range(n_dev):
        rs[r][n_dev - 1] = r
    for s in range(n_dev - 2, -1, -1):
        for src, dst in perm:
            rs[src][s] = rs[dst][s + 1]
    return nbr, ag, rs


# ---------------------------------------------------------------------------
# capture: grab the kernel/grid/specs from the REAL wrapper call
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Captured:
    kernel: Callable
    grid: Tuple[int, ...]
    in_specs: Sequence
    out_specs: object
    out_shape: object
    scratch_shapes: Sequence
    operands: Tuple

    def outputs(self) -> List[Tuple[object, jax.ShapeDtypeStruct]]:
        """(spec, shape) per output: the result first, then any HBM work
        buffers a kernel declares as extra outputs (``A_agg``, in-flight
        partials)."""
        if isinstance(self.out_shape, (tuple, list)):
            return list(zip(self.out_specs, self.out_shape))
        return [(self.out_specs, self.out_shape)]


@contextlib.contextmanager
def _capture_pallas_call(box: Dict):
    """Patch ``compat.pallas_call`` so invoking a kernel wrapper records the
    call instead of executing it (outputs come back as zeros so wrapper
    epilogue code — reshapes etc. — still runs)."""
    from repro import compat

    def fake_pallas_call(kernel, *, grid, in_specs, out_specs, out_shape,
                         scratch_shapes=(), **_kw):
        def call(*operands):
            box["cap"] = Captured(kernel=kernel, grid=tuple(grid),
                                  in_specs=tuple(in_specs),
                                  out_specs=out_specs, out_shape=out_shape,
                                  scratch_shapes=tuple(scratch_shapes),
                                  operands=operands)
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                out_shape)
        return call

    orig = compat.pallas_call
    compat.pallas_call = fake_pallas_call
    try:
        yield box
    finally:
        compat.pallas_call = orig


# ---------------------------------------------------------------------------
# shim refs, regions, events
# ---------------------------------------------------------------------------
def _as_int(x) -> int:
    return int(x)


def _norm_index(shape: Tuple[int, ...], idx) -> Tuple[Tuple[int, int], ...]:
    """Concrete (start, size) per dim for an ``.at[...]``/getitem index."""
    if not isinstance(idx, tuple):
        idx = (idx,)
    if any(i is Ellipsis for i in idx):
        pos = idx.index(Ellipsis)
        fill = len(shape) - (len(idx) - 1)
        idx = idx[:pos] + (slice(None),) * fill + idx[pos + 1:]
    dims: List[Tuple[int, int]] = []
    for d, size in enumerate(shape):
        if d < len(idx):
            i = idx[d]
            if isinstance(i, slice):
                start = 0 if i.start is None else _as_int(i.start)
                stop = size if i.stop is None else _as_int(i.stop)
                dims.append((start, stop - start))
            elif hasattr(i, "start") and hasattr(i, "size"):   # pl.ds
                dims.append((_as_int(i.start), _as_int(i.size)))
            else:
                dims.append((_as_int(i), 1))
        else:
            dims.append((0, size))
    return tuple(dims)


def _np_index(shape, idx):
    """The same index, lowered to plain numpy slicing (ints stay ints so
    reads keep the kernel's expected rank)."""
    if not isinstance(idx, tuple):
        idx = (idx,)
    out = []
    for i in idx:
        if i is Ellipsis or isinstance(i, slice):
            out.append(i if isinstance(i, slice) else Ellipsis)
        elif hasattr(i, "start") and hasattr(i, "size"):
            out.append(slice(_as_int(i.start), _as_int(i.start) + _as_int(i.size)))
        else:
            out.append(_as_int(i))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class Region:
    dims: Tuple[Tuple[int, int], ...]

    def overlaps(self, other: "Region") -> bool:
        for (s1, n1), (s2, n2) in zip(self.dims, other.dims):
            if s1 + n1 <= s2 or s2 + n2 <= s1:
                return False
        return True

    def size(self) -> int:
        n = 1
        for _, sz in self.dims:
            n *= sz
        return n

    def __str__(self):
        return "[" + ", ".join(f"{s}:{s + n}" for s, n in self.dims) + "]"


@dataclasses.dataclass
class Event:
    kind: str                 # read | write | remote_start | wait_send | wait_recv
    rank: int
    where: str                # provenance: kernel/cell
    buf: str = ""
    region: Optional[Region] = None
    sem: str = ""
    send_sem: str = ""
    nbytes: int = 0
    dst_rank: int = -1
    dst_buf: str = ""
    dst_region: Optional[Region] = None


class _Sem:
    """A DMA semaphore, or an array of them (``sem.at[i, j]`` names one)."""

    def __init__(self, name: str):
        self.name = name

    @property
    def at(self):
        return self

    def __getitem__(self, idx):
        idx = idx if isinstance(idx, tuple) else (idx,)
        return _Sem(f"{self.name}[{','.join(str(_as_int(i)) for i in idx)}]")


def _is_sem(entry) -> bool:
    """A ``scratch_shapes`` entry that allocates DMA semaphore(s)."""
    from repro import compat
    return (entry is compat.DMA_SEM or isinstance(entry, type(compat.DMA_SEM))
            or "semaphore" in str(getattr(entry, "memory_space", "")).lower())


class _Ref:
    """Shim standing in for one kernel Ref.

    ``space`` is "any" (HBM operand / scratch — race- and ring-tracked),
    "vmem"/"smem" (per-cell private — untracked), or a blocked spec
    (fresh block backing per cell, global coverage mapping for outputs).
    Backing arrays are REAL-shaped zeros so every jnp op in the kernel body
    sees the exact shapes the compiled kernel would.
    """

    def __init__(self, name, shape, dtype, space, rec, *, backing=None,
                 is_output=False, block_origin=None):
        self.name = name
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self.space = space
        self._rec = rec
        self._backing = (backing if backing is not None
                         else jnp.zeros(self.shape, self.dtype))
        self.is_output = is_output
        self.block_origin = block_origin      # global offset of this block

    # -- direct indexing ----------------------------------------------------
    def __getitem__(self, idx):
        if self.space == "any":
            self._rec.access("read", self, Region(_norm_index(self.shape, idx)))
        return self._backing[_np_index(self.shape, idx)]

    def __setitem__(self, idx, _val):
        region = Region(_norm_index(self.shape, idx))
        if self.space == "any":
            self._rec.access("write", self, region)
        if self.is_output:
            self._rec.cover(self, region)

    # -- .at[...] views (copy endpoints) ------------------------------------
    @property
    def at(self):
        return _At(self)


class _At:
    def __init__(self, ref: _Ref):
        self._ref = ref

    def __getitem__(self, idx):
        return _View(self._ref, Region(_norm_index(self._ref.shape, idx)))


@dataclasses.dataclass
class _View:
    ref: _Ref
    region: Region

    @property
    def nbytes(self) -> int:
        return self.region.size() * self.ref.dtype.itemsize


def _as_view(x) -> _View:
    if isinstance(x, _View):
        return x
    return _View(x, Region(tuple((0, d) for d in x.shape)))


class _LocalCopy:
    def __init__(self, rec, src, dst, sem):
        self._rec = rec
        self.src, self.dst = _as_view(src), _as_view(dst)
        self.sem = sem
        self.started = self.waited = False
        self.where = rec.where()
        rec.local_copies.append(self)

    def start(self):
        self.started = True
        if self.src.nbytes != self.dst.nbytes:
            self._rec.err(f"local async copy size mismatch: "
                          f"{self.src.ref.name}{self.src.region} "
                          f"({self.src.nbytes}B) -> {self.dst.ref.name}"
                          f"{self.dst.region} ({self.dst.nbytes}B)")
        self._rec.access_view("read", self.src)
        self._rec.access_view("write", self.dst)

    def wait(self):
        if not self.started:
            self._rec.err("wait() on a local async copy that was never "
                          "started")
        self.waited = True


class _RemoteCopy:
    """Descriptor shim for ``make_async_remote_copy`` — the kernels build
    fresh descriptors to wait on copies started elsewhere, so only the
    events matter, matched by (rank, semaphore) FIFO in the replay."""

    def __init__(self, rec, src_ref, dst_ref, send_sem, recv_sem, device_id):
        self._rec = rec
        self.src, self.dst = _as_view(src_ref), _as_view(dst_ref)
        self.send_sem, self.recv_sem = send_sem, recv_sem
        self.device_id = _as_int(device_id)

    def start(self):
        self._rec.access_view("read", self.src)
        self._rec.event(Event(
            kind="remote_start", rank=self._rec.rank, where=self._rec.where(),
            buf=self.src.ref.name, region=self.src.region,
            sem=self.recv_sem.name, send_sem=self.send_sem.name,
            nbytes=self.src.nbytes,
            dst_rank=self.device_id, dst_buf=self.dst.ref.name,
            dst_region=self.dst.region))

    def wait_send(self):
        self._rec.event(Event(kind="wait_send", rank=self._rec.rank,
                              where=self._rec.where(),
                              sem=self.send_sem.name))

    def wait_recv(self):
        self._rec.event(Event(kind="wait_recv", rank=self._rec.rank,
                              where=self._rec.where(), buf=self.dst.ref.name,
                              region=self.dst.region, sem=self.recv_sem.name,
                              nbytes=self.dst.nbytes))


class _Recorder:
    """Per-rank event stream + output-coverage counters + trace errors."""

    def __init__(self, label: str, rank: int, out_shape):
        self.label = label
        self.rank = rank
        self.cell: Tuple[int, ...] = ()
        self.events: List[Event] = []
        self.errors: List[str] = []
        self.local_copies: List[_LocalCopy] = []
        self.coverage = np.zeros(out_shape.shape, np.int32)

    def where(self) -> str:
        step = f"step={self.cell[0]} " if self.cell else ""
        return f"{self.label} rank{self.rank} {step}cell={self.cell}"

    def err(self, msg: str):
        self.errors.append(f"{self.where()}: {msg}")

    def event(self, e: Event):
        self.events.append(e)

    def access(self, kind: str, ref: _Ref, region: Region):
        self.events.append(Event(kind=kind, rank=self.rank,
                                 where=self.where(), buf=ref.name,
                                 region=region))

    def access_view(self, kind: str, view: _View):
        if view.ref.space == "any":
            self.access(kind, view.ref, view.region)
        if kind == "write" and view.ref.is_output:
            self.cover(view.ref, view.region)

    def cover(self, ref: _Ref, region: Region):
        dims = region.dims
        if ref.block_origin is not None:
            dims = tuple((o + s, n)
                         for o, (s, n) in zip(ref.block_origin, dims))
        self.coverage[tuple(slice(s, s + n) for s, n in dims)] += 1

    def finish_cells(self):
        for cp in self.local_copies:
            if cp.started and not cp.waited:
                self.errors.append(
                    f"{cp.where}: local async copy "
                    f"{cp.src.ref.name}{cp.src.region} -> "
                    f"{cp.dst.ref.name}{cp.dst.region} started but never "
                    "waited (unbalanced local DMA semaphore)")


# ---------------------------------------------------------------------------
# abstract per-rank execution of the captured grid program
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def _patched_primitives(rec: _Recorder, grid: Tuple[int, ...]):
    from jax import lax
    from jax.experimental import pallas as pl
    from repro import compat

    saved = (pl.program_id, pl.num_programs, pl.when,
             compat.make_async_copy, compat.make_async_remote_copy,
             lax.axis_index)

    def program_id(axis):
        return rec.cell[axis]

    def num_programs(axis):
        return grid[axis]

    def when(pred):
        def deco(fn):
            if bool(pred):
                fn()
            return fn
        return deco

    def axis_index(_axis):
        return rec.rank

    def make_async_copy(src, dst, sem):
        return _LocalCopy(rec, src, dst, sem)

    def make_async_remote_copy(*, src_ref, dst_ref, send_sem, recv_sem,
                               device_id, device_id_type=None):
        del device_id_type
        return _RemoteCopy(rec, src_ref, dst_ref, send_sem, recv_sem,
                           device_id)

    pl.program_id, pl.num_programs, pl.when = (program_id, num_programs,
                                               when)
    compat.make_async_copy = make_async_copy
    compat.make_async_remote_copy = make_async_remote_copy
    lax.axis_index = axis_index
    try:
        yield
    finally:
        (pl.program_id, pl.num_programs, pl.when, compat.make_async_copy,
         compat.make_async_remote_copy, lax.axis_index) = saved


def _spec_space(spec) -> str:
    ms = getattr(spec, "memory_space", None)
    s = str(ms).lower() if ms is not None else "any"
    for known in ("smem", "vmem", "any"):
        if known in s:
            return known
    return "any" if spec.block_shape is None else "vmem"


def _build_static_args(cap: Captured, rec: _Recorder):
    """Shims for the non-blocked args (built once per rank): ANY/SMEM
    operands, the unblocked output, and every scratch entry."""

    ins = []
    blocked_in: List[Tuple[int, object, object]] = []   # (argpos, spec, op)
    for i, (spec, op) in enumerate(zip(cap.in_specs, cap.operands)):
        if spec.block_shape is None:
            space = _spec_space(spec)
            backing = jnp.asarray(op) if space == "smem" else None
            ins.append(_Ref(f"in{i}", op.shape, op.dtype, space, rec,
                            backing=backing))
        else:
            ins.append(None)
            blocked_in.append((i, spec, op))
    (out_spec, out_shape), *work = cap.outputs()
    if out_spec.block_shape is None:
        out = _Ref("out", out_shape.shape, out_shape.dtype, "any",
                   rec, is_output=True)
    else:
        out = None
    work = [_Ref(f"work{i}", shape.shape, shape.dtype, "any", rec)
            for i, (_spec, shape) in enumerate(work)]
    scratch = []
    for i, entry in enumerate(cap.scratch_shapes):
        if _is_sem(entry):
            scratch.append(_Sem(f"sem{i}"))
        else:
            space = str(getattr(entry, "memory_space", "vmem")).lower()
            space = "any" if "any" in space else (
                "smem" if "smem" in space else "vmem")
            scratch.append(_Ref(f"scratch{i}", entry.shape, entry.dtype,
                                space, rec))
    return ins, blocked_in, out, work + scratch


def _trace_rank(cap: Captured, label: str, rank: int) -> _Recorder:
    """Run the kernel body for every grid cell on one logical rank."""
    out_spec, out_shape = cap.outputs()[0]
    rec = _Recorder(label, rank, out_shape)
    ins, blocked_in, out_static, scratch = _build_static_args(cap, rec)
    out_blocked = out_spec.block_shape is not None

    with _patched_primitives(rec, cap.grid):
        for cell in itertools.product(*(range(g) for g in cap.grid)):
            rec.cell = cell
            args = list(ins)
            for pos, spec, op in blocked_in:
                idx = tuple(_as_int(i) for i in spec.index_map(*cell))
                args[pos] = _Ref(f"in{pos}", spec.block_shape, op.dtype,
                                 "vmem", rec)
            if out_blocked:
                spec = out_spec
                idx = tuple(_as_int(i) for i in spec.index_map(*cell))
                origin = tuple(b * i for b, i in zip(spec.block_shape, idx))
                out = _Ref("out", spec.block_shape, out_shape.dtype,
                           "vmem", rec, is_output=True, block_origin=origin)
            else:
                out = out_static
            cap.kernel(*args, out, *scratch)
    rec.finish_cells()
    return rec


# ---------------------------------------------------------------------------
# contract 1+2 machinery: scheduler replay + vector-clock happens-before
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Landed:
    dst_rank: int
    buf: str
    region: Region
    nbytes: int
    start_vc: np.ndarray
    where: str
    sealed_vc: Optional[np.ndarray] = None
    sealed_where: str = ""


@dataclasses.dataclass
class _Access:
    rank: int
    kind: str
    buf: str
    region: Region
    vc: np.ndarray
    where: str


def _vc_leq(a: np.ndarray, b: np.ndarray) -> bool:
    return bool(np.all(a <= b))


def _replay(label: str, n_dev: int, streams: List[List[Event]]):
    """Deterministic scheduler replay of the per-rank event streams.

    Enabledness: reads/writes/remote starts always run; ``wait_send`` needs
    an undrained started send on (rank, sem); ``wait_recv`` needs an
    unconsumed arrival on (rank, sem) — FIFO per semaphore, matching the
    hardware's DMA completion counting.  A global stall is a protocol
    deadlock (a wait whose signal can never arrive).  Returns
    (errors, accesses, landed copies) for the race pass.
    """
    errs: List[str] = []
    pcs = [0] * n_dev
    vcs = [np.zeros(n_dev, np.int64) for _ in range(n_dev)]
    channel: Dict[Tuple[int, str], List[_Landed]] = {}
    sendq: Dict[Tuple[int, str], List[str]] = {}
    accesses: List[_Access] = []
    landed: List[_Landed] = []

    def tick(r: int) -> np.ndarray:
        vcs[r][r] += 1
        return vcs[r].copy()

    while True:
        progressed = False
        done = True
        for r in range(n_dev):
            if pcs[r] >= len(streams[r]):
                continue
            done = False
            e = streams[r][pcs[r]]
            if e.kind in ("read", "write"):
                accesses.append(_Access(r, e.kind, e.buf, e.region, tick(r),
                                        e.where))
            elif e.kind == "remote_start":
                vc = tick(r)
                c = _Landed(dst_rank=e.dst_rank, buf=e.dst_buf,
                            region=e.dst_region, nbytes=e.nbytes,
                            start_vc=vc, where=e.where)
                channel.setdefault((e.dst_rank, e.sem), []).append(c)
                sendq.setdefault((r, e.send_sem), []).append(e.where)
            elif e.kind == "wait_send":
                q = sendq.get((r, e.sem), [])
                if not q:
                    continue                      # blocked
                q.pop(0)
                tick(r)
            elif e.kind == "wait_recv":
                q = channel.get((r, e.sem), [])
                if not q:
                    continue                      # blocked
                c = q.pop(0)
                if c.nbytes != e.nbytes or c.buf != e.buf or \
                        c.region != e.region:
                    errs.append(
                        f"{e.where}: wait_recv descriptor "
                        f"({e.buf}{e.region}, {e.nbytes}B) does not match "
                        f"the arriving copy ({c.buf}{c.region}, "
                        f"{c.nbytes}B) started at {c.where}")
                vcs[r] = np.maximum(vcs[r], c.start_vc)
                c.sealed_vc = tick(r)
                c.sealed_where = e.where
                landed.append(c)
            else:                                  # pragma: no cover
                raise AssertionError(e.kind)
            pcs[r] += 1
            progressed = True
        if done:
            break
        if not progressed:
            for r in range(n_dev):
                if pcs[r] < len(streams[r]):
                    e = streams[r][pcs[r]]
                    errs.append(
                        f"{e.where}: deadlock — {e.kind} on {e.sem!r} can "
                        "never be satisfied (no matching DMA start reaches "
                        "this semaphore)")
            return errs, accesses, landed

    for (rank, sem), q in channel.items():
        for c in q:
            errs.append(f"{c.where}: remote copy into rank{rank} "
                        f"{c.buf}{c.region} arrived but its recv semaphore "
                        f"{sem!r} is never waited (unbalanced recv)")
            landed.append(c)                      # still a write: race-check
    for (rank, sem), q in sendq.items():
        for where in q:
            errs.append(f"{where}: send on {sem!r} never drained by a "
                        "wait_send before kernel exit (unbalanced send)")
    return errs, accesses, landed


def _race_errors(accesses: List[_Access], landed: List[_Landed]) -> List[str]:
    """Contract 2: every DMA landing must be happens-before ordered against
    every local access of its slot (through the recv wait), and no two
    unordered DMAs may write overlapping slots."""
    errs: List[str] = []
    for c in landed:
        for a in accesses:
            if a.rank != c.dst_rank or a.buf != c.buf:
                continue
            if not a.region.overlaps(c.region):
                continue
            before = _vc_leq(a.vc, c.start_vc)
            after = c.sealed_vc is not None and _vc_leq(c.sealed_vc, a.vc)
            if not (before or after):
                errs.append(
                    f"{a.where}: {a.kind} of slot {a.buf}{a.region} races "
                    f"the DMA landing started at {c.where} (no "
                    "happens-before through the arriving step's recv wait)")
    for c1, c2 in itertools.combinations(landed, 2):
        if c1.dst_rank != c2.dst_rank or c1.buf != c2.buf:
            continue
        if not c1.region.overlaps(c2.region):
            continue
        o12 = c1.sealed_vc is not None and _vc_leq(c1.sealed_vc, c2.start_vc)
        o21 = c2.sealed_vc is not None and _vc_leq(c2.sealed_vc, c1.start_vc)
        if not (o12 or o21):
            errs.append(
                f"{c1.where} and {c2.where}: slot {c1.buf}{c1.region} "
                "written by two unordered DMAs (each slot must have exactly "
                "one in-flight writer)")
    return errs


# ---------------------------------------------------------------------------
# contract 3: ring arithmetic vs the overlap.py reference schedule
# ---------------------------------------------------------------------------
def _ring_errors(label: str, kind: str, n_dev: int, reverse: bool,
                 recs: List[_Recorder], slot_rows: int) -> List[str]:
    """``slot_rows``: rows of one ring slot in the buffer the owner index is
    read from (ag: the A_agg slot dim is explicit; rs: the A operand's rows
    per output shard, ``m_sh``)."""
    nbr, ag_owner, rs_owner = ring_schedules(n_dev, reverse)
    errs: List[str] = []
    for rec in recs:
        me = rec.rank
        for e in rec.events:
            step = int(e.where.split("step=")[1].split(" ")[0]) \
                if "step=" in e.where else 0
            if e.kind == "remote_start":
                if e.dst_rank != nbr[me]:
                    errs.append(
                        f"{e.where}: remote copy targets rank {e.dst_rank} "
                        f"but the {'reverse' if reverse else 'forward'} "
                        f"ring neighbor of rank {me} is {nbr[me]} "
                        "(overlap._ring_perm reference)")
                if kind == "ag":
                    slot = e.region.dims[0][0]
                    want = ag_owner[me][step]
                    if slot != want:
                        errs.append(
                            f"{e.where}: forwards A_agg slot {slot} but the "
                            f"reference schedule holds shard {want} at step "
                            f"{step}")
                else:
                    src_slot, dst_slot = (e.region.dims[0][0],
                                          e.dst_region.dims[0][0])
                    if (src_slot, dst_slot) != (step, step + 1):
                        errs.append(
                            f"{e.where}: rs forwards in-flight slot "
                            f"{src_slot}->{dst_slot}; the decomposed ring "
                            f"expects {step}->{step + 1}")
            elif e.kind == "read" and kind == "ag" and e.buf.startswith("work"):
                slot = e.region.dims[0][0]
                want = ag_owner[me][step]
                if slot != want:
                    errs.append(
                        f"{e.where}: computes on A_agg slot {slot} but rank "
                        f"{me} holds shard {want} at step {step} "
                        "(overlap.py ring reference)")
            elif e.kind == "read" and kind == "rs" and e.buf == "in0":
                owner = e.region.dims[0][0] // max(slot_rows, 1)
                want = rs_owner[me][step]
                if owner != want:
                    errs.append(
                        f"{e.where}: contracts rows of output owner {owner} "
                        f"but the reference swizzle computes owner {want} "
                        f"at step {step}")
    return errs


# ---------------------------------------------------------------------------
# contract 4+5: coverage and budget
# ---------------------------------------------------------------------------
def _coverage_errors(label: str, recs: List[_Recorder]) -> List[str]:
    errs = []
    for rec in recs:
        cov = rec.coverage
        if (cov == 1).all():
            continue
        missed = int((cov == 0).sum())
        dup = int((cov > 1).sum())
        idx = tuple(int(i) for i in
                    np.argwhere(cov != 1)[0]) if cov.size else ()
        errs.append(
            f"{label} rank{rec.rank}: output tile coverage broken — "
            f"{missed} element(s) never written, {dup} written more than "
            f"once (first bad element at {idx}; every [bm,bn] tile must be "
            "written exactly once across the grid)")
    return errs


def traced_vmem_bytes(cap: Captured) -> int:
    """VMEM footprint of a captured call: VMEM scratch + 2x every blocked
    in/out block (Pallas double-buffers blocked refs across grid steps)."""
    total = 0
    for entry in cap.scratch_shapes:
        if _is_sem(entry):
            continue
        if "vmem" in str(getattr(entry, "memory_space", "vmem")).lower():
            total += int(np.prod(entry.shape)) * np.dtype(entry.dtype).itemsize
    for spec, op in list(zip(cap.in_specs, cap.operands)) + cap.outputs():
        if spec.block_shape is not None:
            total += 2 * int(np.prod(spec.block_shape)) * \
                np.dtype(op.dtype).itemsize
    return total


def _budget_errors(label: str, cap: Captured) -> List[str]:
    errs = []
    vmem = traced_vmem_bytes(cap)
    if vmem > VMEM_LIMIT_BYTES:
        errs.append(
            f"{label}: static VMEM footprint {vmem / 2**20:.1f} MiB exceeds "
            f"the {VMEM_LIMIT_BYTES / 2**20:.0f} MiB per-core budget — "
            "infeasible tiling (shrink bm/bk/bn)")
    smem = 0
    for spec, op in zip(cap.in_specs, cap.operands):
        if spec.block_shape is None and _spec_space(spec) == "smem":
            smem += op.size * np.dtype(op.dtype).itemsize
    for entry in cap.scratch_shapes:
        if _is_sem(entry):
            continue
        if "smem" in str(getattr(entry, "memory_space", "")).lower():
            smem += int(np.prod(entry.shape)) * np.dtype(entry.dtype).itemsize
    if smem > SMEM_LIMIT_BYTES:
        errs.append(f"{label}: SMEM footprint {smem} B exceeds the "
                    f"{SMEM_LIMIT_BYTES} B scalar-memory budget")
    return errs


# ---------------------------------------------------------------------------
# top level: check one call, the registry, the gate entry point
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One (kernel, direction, shape cell) to verify.

    ``build`` invokes the REAL wrapper (under the capture patch) — the
    checker never reimplements a call site.  ``kind`` is "ag"/"rs" for ring
    kernels (enables the ring-arithmetic contract; ``n_dev`` ranks are
    traced) and None for single-device grid kernels.  ``slot_rows`` maps
    buffer rows to ring slots for the rs owner check.
    """
    label: str
    build: Callable[[], object]
    kind: Optional[str] = None
    n_dev: int = 1
    reverse: bool = False
    slot_rows: int = 0


def check_case(case: KernelCase) -> List[str]:
    """All five contract classes for one kernel call."""
    box: Dict = {}
    try:
        with _capture_pallas_call(box):
            case.build()
    except Exception as e:                        # a call that cannot build
        return [f"{case.label}: capture failed: {type(e).__name__}: {e}"]
    if "cap" not in box:
        return [f"{case.label}: wrapper never reached compat.pallas_call"]
    cap = box["cap"]

    errs = _budget_errors(case.label, cap)
    cells = int(np.prod(cap.grid)) if cap.grid else 0
    if cells > MAX_GRID_CELLS:
        errs.append(f"{case.label}: grid {cap.grid} has {cells} cells — "
                    f"above the {MAX_GRID_CELLS}-cell static-trace cap; "
                    "use a smaller shape cell (contracts are structural)")
        return errs

    recs = []
    for rank in range(case.n_dev):
        try:
            recs.append(_trace_rank(cap, case.label, rank))
        except Exception as e:
            errs.append(f"{case.label} rank{rank}: abstract execution "
                        f"failed: {type(e).__name__}: {e}")
            return errs
    for rec in recs:
        errs.extend(rec.errors)

    replay_errs, accesses, landed = _replay(
        case.label, case.n_dev, [r.events for r in recs])
    errs.extend(replay_errs)
    errs.extend(_race_errors(accesses, landed))
    if case.kind in ("ag", "rs"):
        errs.extend(_ring_errors(case.label, case.kind, case.n_dev,
                                 case.reverse, recs, case.slot_rows))
    errs.extend(_coverage_errors(case.label, recs))
    return errs


# -- in-tree kernel registry -------------------------------------------------
_REGISTRY: List[Callable[[Optional[Sequence[str]]], List[KernelCase]]] = []


def register(case_builder: Callable[[Optional[Sequence[str]]],
                                    List[KernelCase]]):
    """Register a case builder: ``configs -> [KernelCase]``.  New kernels
    add themselves here so ``--kernels`` picks them up automatically."""
    _REGISTRY.append(case_builder)
    return case_builder


def _ring_shape_cells(config_names: Optional[Sequence[str]]
                      ) -> List[Tuple[str, int, int, int]]:
    """Config-derived per-device GEMM cells (kind, gm, gk, gn), deduped.

    Mirrors ``autotune.candidate_space``'s flux branch: the smoke config's
    ``model_seam_shapes`` give the seam GEMMs, divided onto the tp ring.
    Smoke token counts keep every grid a few dozen cells.
    """
    from repro.analysis.seamcheck import discover_configs
    from repro.configs.base import ParallelConfig, get_smoke_config
    from repro.tuning.autotune import model_seam_shapes

    par = ParallelConfig(tp=_TP, dp=1)
    cells: List[Tuple[str, int, int, int]] = []
    seen = set()
    for name in (config_names or discover_configs()):
        cfg = get_smoke_config(name)
        for _key, (kind, m, n, k) in model_seam_shapes(
                cfg, par, tokens_per_dp=128, decode_batch=8).items():
            if kind == "ag":
                gm, gk, gn = max(m // _TP, 1), k, max(n // _TP, 1)
            elif kind == "rs":
                gm, gk, gn = max(m // _TP, 1), max(k // _TP, 1), n
            else:
                continue
            cell = (kind, gm, gk, gn)
            if cell not in seen:
                seen.add(cell)
                cells.append(cell)
    return cells


def _half_blocks(gm: int, gk: int, gn: int) -> Tuple[int, int, int]:
    """Blocks at half the cell dims: guarantees a multi-tile grid on every
    axis that can afford one, so the swizzle/accumulator logic is actually
    exercised (full-dim blocks would collapse the inner grid to 1x1x1)."""
    def half(d: int) -> int:
        # the checked schedule holds for any dividing block, so these need
        # not be (16, 128)-aligned like the blocks ``ops.plan_blocks`` runs
        b = max(d // 2, 1)
        while d % b:
            b -= 1
        return b
    return half(gm), half(gk), half(gn)


@register
def _flux_ring_cases(config_names=None) -> List[KernelCase]:
    from repro.kernels.ag_gemm import ag_gemm
    from repro.kernels.gemm_rs import gemm_rs

    cases = []
    for kind, gm, gk, gn in _ring_shape_cells(config_names):
        bm, bk, bn = _half_blocks(gm, gk, gn)
        for reverse in (False, True):
            tag = "rev" if reverse else "fwd"
            if kind == "ag":
                a = jnp.zeros((gm, gk), jnp.bfloat16)
                b = jnp.zeros((gk, gn), jnp.bfloat16)
                bias = jnp.zeros((gn,), jnp.bfloat16)

                def build(a=a, b=b, bias=bias, blocks=(bm, bk, bn),
                          reverse=reverse):
                    return ag_gemm(a, b, axis_name=_AXIS, n_dev=_TP,
                                   bm=blocks[0], bk=blocks[1], bn=blocks[2],
                                   reverse=reverse, activation="silu",
                                   bias=bias)

                cases.append(KernelCase(
                    label=f"ag_gemm[{tag}]@({gm}x{gk}x{gn})b({bm},{bk},{bn})",
                    build=build, kind="ag", n_dev=_TP, reverse=reverse,
                    slot_rows=gm))
            else:
                a = jnp.zeros((_TP * gm, gk), jnp.bfloat16)
                b = jnp.zeros((gk, gn), jnp.bfloat16)

                def build(a=a, b=b, blocks=(bm, bk, bn), reverse=reverse):
                    return gemm_rs(a, b, axis_name=_AXIS, n_dev=_TP,
                                   bm=blocks[0], bk=blocks[1], bn=blocks[2],
                                   reverse=reverse)

                cases.append(KernelCase(
                    label=f"gemm_rs[{tag}]@({gm}x{gk}x{gn})b({bm},{bk},{bn})",
                    build=build, kind="rs", n_dev=_TP, reverse=reverse,
                    slot_rows=gm))
    return cases


@register
def _attention_cases(config_names=None) -> List[KernelCase]:
    del config_names        # attention grids are config-shape independent
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mla_decode import mla_decode_attention

    cases = []
    q = jnp.zeros((1, 4, 128, 32), jnp.bfloat16)
    kv = jnp.zeros((1, 2, 128, 32), jnp.bfloat16)
    cases.append(KernelCase(
        label="flash_attention[causal]@(b1,hq4,hkv2,s128,d32)bq32",
        build=lambda: flash_attention(q, kv, kv, causal=True, bq=32,
                                      bkv=32)))
    qc = jnp.zeros((1, 4, 64, 32), jnp.bfloat16)
    kc = jnp.zeros((1, 2, 128, 32), jnp.bfloat16)
    cases.append(KernelCase(
        label="flash_attention[chunk]@(sq64,skv128,off64)bq32",
        build=lambda: flash_attention(qc, kc, kc, causal=True, bq=32,
                                      bkv=32, kv_offset=64)))
    qe = jnp.zeros((2, 4, 32), jnp.bfloat16)
    qr = jnp.zeros((2, 4, 16), jnp.bfloat16)
    cc = jnp.zeros((2, 128, 32), jnp.bfloat16)
    kr = jnp.zeros((2, 128, 16), jnp.bfloat16)
    vl = jnp.full((2,), 128, jnp.int32)
    cases.append(KernelCase(
        label="mla_decode[absorbed]@(b2,h4,r32,s128)bs32",
        build=lambda: mla_decode_attention(qe, qr, cc, kr, vl, scale=1.0,
                                           bs=32)))
    return cases


def run_kernel_checks(config_names: Optional[Sequence[str]] = None,
                      log=None) -> List[str]:
    """The ``--kernels`` gate: every registered kernel x both ring
    directions x the config-derived shape cells."""
    errs: List[str] = []
    for builder in _REGISTRY:
        for case in builder(config_names):
            case_errs = check_case(case)
            if log:
                log(f"  {case.label}: "
                    + ("OK" if not case_errs else
                       f"{len(case_errs)} violation(s)"))
            errs.extend(case_errs)
    return errs
