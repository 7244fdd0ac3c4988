"""From a profiler trace to device busy time, program times and idle gaps.

A trace is first read into plain events, ``(name, start_ns, end_ns)``:

* per device plane, the ``XLA Modules`` line (one event per program
  execution) and the ``XLA Ops`` line (the operations inside them);
* on the host, the benchmark's own ``bench.*`` spans
  (``jax.profiler.TraceAnnotation``), among them ``bench.window``, the
  traced window.

``reduce`` then works on those events alone, so it is tested on a small
recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]

WINDOW = "bench.window"
#: the spans around the calls that dispatch a program, in dispatch order
DISPATCH_SPANS = ("bench.decode_step", "bench.prefill_chunk")
OUTSIDE = "bench loop (no span)"


def read_trace(trace_dir: str) -> Dict:
    """The events of the one ``.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    out: Dict = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            dev = {"modules": [], "ops": []}
            for ln in lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(ln.name)
                if key:
                    dev[key] = [(ev.name, int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns))
                                for ev in ln.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for ln in lines:
                out["host"].extend(
                    (ev.name, int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in ln.events if ev.name.startswith("bench."))
    return out


def dump(events: Dict, path: str, seconds: float = 1.2,
         per_line: int = 400) -> None:
    """Write the first ``seconds`` of the traced window as JSON, ending at
    the last whole tick, each device line cut to its first ``per_line``
    events and op names to ``op_label`` (the small recorded traces the
    reduction's tests read)."""
    import json
    _, lo, hi = next(sp for sp in events["host"] if sp[0] == WINDOW)
    cut = lo + int(seconds * 1e9)
    ticks = [sp[2] for sp in events["host"]
             if sp[0] == "bench.tick" and lo <= sp[1] and sp[2] <= cut]
    hi = (ticks[-1] if ticks else min(hi, cut)) + 1000
    small = {"lines": {k: v for k, v in events["lines"].items()
                       if k.startswith("/device:")},
             "host": [[WINDOW, lo, hi]] + [
                 list(sp) for sp in events["host"]
                 if sp[0] != WINDOW and lo <= sp[1] and sp[2] <= hi],
             "devices": {k: {n: [[op_label(e[0]), e[1], e[2]] for e in v
                                 if e[1] < hi and e[2] > lo][:per_line]
                             for n, v in d.items()}
                         for k, d in events["devices"].items()}}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(small, f)


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping [start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _span_at(spans: Sequence[Event], t: int) -> str:
    """The innermost benchmark span around time ``t``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp[0] != WINDOW and sp[1] <= t < sp[2]:
            if best is None or sp[1] >= best[1]:
                best = sp
    return best[0] if best else OUTSIDE


def op_label(name: str) -> str:
    """``%fusion.3 = f32[16,64]{...} fusion(...)`` -> ``%fusion.3
    f32[16,64] fusion``: the op, its result type and its opcode; a seam
    kernel's call is named by ``seam_kernel``."""
    k = seam_kernel(name)
    if k:
        return f"{name.split(' ')[0]} {k}"
    m = re.match(r"^(%?[\w.-]+) = (\(?[\w\[\],]+)\S* ([\w-]+)", name)
    return " ".join(m.groups()) if m else name[:120]


#: a Pallas call whose result is (output [rows, n], buffer [chips, m, k])
_SEAM = re.compile(r"^%?\S+ = \(\w+\[(\d+),\d+\]\{[^}]*\}, "
                   r"\w+\[(\d+),(\d+),\d+\]\{[^}]*\}\) custom-call\(")


def seam_kernel(name: str) -> Optional[str]:
    """Which fused seam kernel a device op runs, from its result types.
    The Pallas calls carry no name; both return their output and an HBM
    buffer of one ``[m, k]`` block per chip.  An AllGather-GEMM's output
    holds every chip's rows (``chips * m``), a GEMM-ReduceScatter's its
    own share (``m``)."""
    hit = _SEAM.match(name)
    if not hit:
        return None
    rows, chips, m = (int(g) for g in hit.groups())
    if rows == chips * m:
        return "ag_gemm"
    if rows == m:
        return "gemm_rs"
    return None


#: the collective operations; an asynchronous one runs from its ``-start``
#: to its ``-done``
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
#: a collective's opcode: after the result type and before its operands
#: (``%psum.3 = bf16[16] all-reduce(...)``), last in an ``op_label``, or
#: the instruction's own name where XLA named it by its opcode; an operand
#: (``fusion(%all-reduce.2)``) is none
_COLLECTIVE = re.compile(r"(?:^%|\s)(" + "|".join(COLLECTIVES)
                         + r")(-start|-done)?(?=[(.\s]|$)")


def collective(name: str) -> Optional[Tuple[str, str]]:
    """``(operation, phase)`` of a device op that is a collective, the
    phase ``"start"``, ``"done"`` or ``""`` for a synchronous one; None for
    any other op."""
    hit = _COLLECTIVE.search(name)
    if not hit:
        return None
    return hit.group(1), (hit.group(2) or "-")[1:]


def kernel_times(reduced: Dict) -> Dict[str, List[float]]:
    """Seconds of each seam kernel execution on the first device in the
    window, by kernel."""
    if reduced is None or not reduced.get("leaf_ops"):
        return {}
    dev = sorted(reduced["leaf_ops"])[0]
    out: Dict[str, List[float]] = {}
    for name, s, e in reduced["leaf_ops"][dev]:
        k = seam_kernel(name)
        if k:
            out.setdefault(k, []).append((e - s) / 1e9)
    return out


def _leaves(ops: Sequence[Event]) -> List[Event]:
    """The ops that hold no other op (a ``while`` holds its body's ops on
    the same line; counting both would count that time twice)."""
    ops = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2] or nxt[2] > op[2]]


def reduce(events: Dict, top: int = 10) -> Dict:
    """Busy and idle time of each device inside the traced window, the
    program executions in order, the operations that took most time and
    the idle gaps by what the host was doing."""
    windows = [sp for sp in events["host"] if sp[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(windows)}")
    _, lo, hi = windows[0]
    spans = [sp for sp in events["host"] if sp[0] != WINDOW]
    busy, modules, op_time, idle, leaves = {}, {}, {}, {}, {}
    for dev, d in sorted(events["devices"].items()):
        ops = _clip(d["ops"] or d["modules"], lo, hi)
        if not ops:
            continue
        merged = union([(s, e) for _, s, e in ops])
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        modules[dev] = sorted(_clip(d["modules"], lo, hi),
                              key=lambda ev: ev[1])
        leaves[dev] = _leaves(ops)
        for name, s, e in leaves[dev]:
            label = op_label(name)
            op_time[label] = op_time.get(label, 0.0) + (e - s) / 1e9
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps = idle.setdefault(dev, {})
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                name = _span_at(spans, (s + e) // 2)
                gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e9
    if not busy:
        raise RuntimeError("the trace holds no device operation in the "
                           "window")
    # operation times and idle gaps are per chip: averaged over the chips
    op_time = {k: v / len(busy) for k, v in op_time.items()}
    gaps: Dict[str, float] = {}
    for per_dev in idle.values():
        for name, s in per_dev.items():
            gaps[name] = gaps.get(name, 0.0) + s / len(busy)
    by_time = lambda kv: -kv[1]
    return {
        "window": (lo, hi),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_by_device": busy,
        "modules": modules,
        "leaf_ops": leaves,
        "dispatch_spans": sorted(
            (sp for sp in spans if sp[0] in DISPATCH_SPANS and lo <= sp[1]
             and sp[2] <= hi), key=lambda sp: sp[1]),
        "device_ops": sorted(op_time.items(), key=by_time)[:top],
        "idle_gaps": sorted(gaps.items(), key=by_time)[:top],
    }


def program_times(reduced: Dict, kinds: Sequence[str]) -> Dict[str, List[float]]:
    """Device seconds spent for each dispatch, by its kind.  The i-th
    dispatch span in the trace is the host's i-th dispatch ``kinds[i]``;
    each program execution on the first device belongs to the last
    dispatch span that started before it (the device runs work in the order
    it was dispatched).  Empty when the spans and dispatches disagree in
    number."""
    spans = reduced["dispatch_spans"]
    if len(spans) != len(kinds) or not spans:
        return {}
    dev = sorted(reduced["modules"])[0]
    starts = [sp[1] for sp in spans]
    per = [0.0] * len(spans)
    for _, s, e in reduced["modules"][dev]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0:
            per[i] += (e - s) / 1e9
    out: Dict[str, List[float]] = {}
    for kind, t in zip(kinds, per):
        out.setdefault(kind, []).append(t)
    return out
