"""The program's own host spans, on the device trace's clock.

The serving runtime marks the phases of each tick with
``jax.profiler.TraceAnnotation``: ``sched.tick`` around the tick,
``sched.admit`` around its admission loop, and ``serve.decode.launch`` /
``.readback`` / ``.emit`` and ``serve.chunk.launch`` / ``.readback`` /
``.emit`` inside ``Server.step`` and ``Server.prefill_chunk``.  The profiler
records them on the same clock as the device planes, so the device's idle
time can be split by what the host was doing in it, exactly, interval by
interval.

``trace.read_trace`` keeps only the benchmark's own ``bench.*`` spans, so
this module reads the program's from the same ``.xplane.pb``
(``read_program``) and ``extend`` adds them to a reduced trace, with each
device's idle intervals.  Nothing here imports the program: a trace of a
program without these spans gives readers nothing to read.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from pbench import trace

#: the name prefixes of the program's own host spans
PREFIXES = ("serve.", "sched.")
#: idle time outside every program span
OUTSIDE = "outside the program's spans"

Interval = Tuple[int, int]


def read_program(trace_dir: str) -> List[trace.Event]:
    """The program's host spans in the one ``.xplane.pb`` under
    ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for plane in data.planes if plane.name.startswith("/host:")
            for ln in plane.lines for ev in ln.events
            if ev.name.startswith(PREFIXES)]


def _window(events: Dict) -> Interval:
    _, lo, hi = next(sp for sp in events["host"] if sp[0] == trace.WINDOW)
    return lo, hi


def extend(reduced: Dict, events: Dict) -> Dict:
    """Add to ``reduced`` (``trace.reduce(events)``, whose keys are left
    as they are) the program's spans clipped to the window,
    ``program_spans``, and each device's idle intervals in the window,
    ``idle``: the complement of the union of its operations, for the
    devices ``reduce`` counted.  ``events["program"]`` holds the spans;
    without it there are none."""
    lo, hi = _window(events)
    reduced["program_spans"] = sorted(
        ((n, max(s, lo), min(e, hi)) for n, s, e in events.get("program", ())
         if e > lo and s < hi), key=lambda sp: (sp[1], -sp[2]))
    idle = {}
    for dev in reduced["busy_by_device"]:
        d = events["devices"][dev]
        busy = trace.union([(max(s, lo), min(e, hi))
                            for _, s, e in d["ops"] or d["modules"]
                            if e > lo and s < hi])
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle[dev] = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                     if e > s]
    reduced["idle"] = idle
    return reduced


def _overlaps(a: Sequence[Interval], b: Sequence[Interval]):
    """``(j, ns)`` for each overlap of an interval of ``a`` with ``b[j]``,
    both sorted lists of disjoint intervals."""
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            yield j, e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1


def exposed_s(reduced: Optional[Dict], names: Iterable[str]
              ) -> Optional[float]:
    """Seconds of device idle time inside the union of the program spans
    named ``names``, intersected exactly, averaged over the chips; None
    when the trace holds no program span."""
    if not reduced or not reduced.get("program_spans"):
        return None
    names = set(names)
    cover = trace.union([(s, e) for n, s, e in reduced["program_spans"]
                         if n in names])
    idle = reduced["idle"]
    return sum(ns for iv in idle.values() for _, ns in _overlaps(iv, cover)
               ) / (1e9 * len(idle))


def counts(reduced: Optional[Dict]) -> Dict[str, int]:
    """How many program spans of each name lie in the window."""
    out: Dict[str, int] = {}
    for n, _, _ in (reduced or {}).get("program_spans", ()):
        out[n] = out.get(n, 0) + 1
    return out


def _innermost(spans: Sequence[trace.Event]) -> List[Tuple[str, int, int]]:
    """The window cut at every span boundary, each piece named by the
    innermost span open over it (the one that began last, or of two that
    began together the one that ends first)."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    starts = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out, open_, k = [], [], 0
    for s, e in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][1] <= s:
            open_.append(starts[k])
            k += 1
        open_ = [sp for sp in open_ if sp[2] > s]
        if open_:
            out.append((max(open_, key=lambda sp: (sp[1], -sp[2]))[0],
                        s, e))
    return out


def attribute(reduced: Optional[Dict]) -> List[Tuple[str, float]]:
    """Device idle seconds by the innermost program span the host was in,
    averaged over the chips, most first; idle time outside every program
    span goes to ``OUTSIDE``.  Empty when the trace holds no program
    span."""
    if not reduced or not reduced.get("program_spans"):
        return []
    pieces = _innermost(reduced["program_spans"])
    cover = [(s, e) for _, s, e in pieces]
    idle = reduced["idle"]
    out: Dict[str, float] = {OUTSIDE: 0.0}
    for iv in idle.values():
        out[OUTSIDE] += sum(e - s for s, e in iv) / (1e9 * len(idle))
        for j, ns in _overlaps(iv, cover):
            name = pieces[j][0]
            out[name] = out.get(name, 0.0) + ns / (1e9 * len(idle))
            out[OUTSIDE] -= ns / (1e9 * len(idle))
    return sorted(out.items(), key=lambda kv: -kv[1])


def window_pct(run, names: Iterable[str]) -> Optional[float]:
    """Idle time inside the named spans as a share of the traced
    window."""
    s = exposed_s(run.reduced, names)
    return None if s is None else 100.0 * s / run.reduced["window_s"]


def per_span_ms(run, names: Iterable[str], per: str) -> Optional[float]:
    """Idle milliseconds inside the named spans per span named ``per``
    in the window."""
    s = exposed_s(run.reduced, names)
    n = counts(run.reduced).get(per, 0)
    return None if s is None or not n else 1e3 * s / n


def dump(events: Dict, path: str, ticks: int = 3,
         max_events: int = 12000) -> None:
    """Write a short window of whole ticks as JSON, in ``trace.dump``'s
    form plus the program's spans under ``program``: from the first tick
    that holds a final chunk (``serve.chunk.readback``), as many ticks as
    ``ticks`` allows while the device lines hold at most ``max_events``
    events, with every device event in it."""
    lo, hi = _window(events)
    program = events.get("program", [])
    ticks_ = sorted(sp[1:] for sp in events["host"]
                    if sp[0] == "bench.tick" and lo <= sp[1] and sp[2] <= hi)
    finals = [s for n, s, _ in program if n == "serve.chunk.readback"]
    first = next((i for i, (s, e) in enumerate(ticks_)
                  if any(s <= t < e for t in finals)), None)
    if first is None:
        raise RuntimeError("no tick in the traced window holds a final "
                           "chunk")

    def inside(a, b):
        return {k: {n: [[trace.op_label(x[0]), x[1], x[2]] for x in v
                        if x[1] < b and x[2] > a]
                    for n, v in d.items()}
                for k, d in events["devices"].items()}

    a = ticks_[first][0] - 1000
    for k in range(min(ticks, len(ticks_) - first), 0, -1):
        b = ticks_[first + k - 1][1] + 1000
        devices = inside(a, b)
        if sum(len(v) for d in devices.values()
               for v in d.values()) <= max_events:
            break
    else:
        raise RuntimeError(f"one tick holds more than {max_events} device "
                           f"events")
    small = {"lines": {k: v for k, v in events["lines"].items()
                       if k.startswith("/device:")},
             "host": [[trace.WINDOW, a, b]] + [
                 list(sp) for sp in events["host"]
                 if sp[0] != trace.WINDOW and a <= sp[1] and sp[2] <= b],
             "program": [list(sp) for sp in program
                         if a <= sp[1] and sp[2] <= b],
             "devices": devices}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(small, f)
