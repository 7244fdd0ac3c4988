"""The program's host spans: emitted by a tiny server, read back from the
profiler's trace, and split against the device's idle time."""
import collections
import json
import os

import numpy as np
import pytest

import pbench_tiny
from pbench import layers, manifest, serving, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))

#: every span the serving runtime emits
SPANS = ("sched.tick", "sched.admit", "serve.decode.launch",
         "serve.decode.readback", "serve.decode.emit", "serve.chunk.launch",
         "serve.chunk.readback", "serve.chunk.emit")
#: the readers of the program's spans, by the cell whose trace they read
READERS = {"chat": ("host_exposed_pct.chat", "decode_launch_exposed_ms.chat",
                    "decode_emit_exposed_ms.chat"),
           "code": ("host_exposed_pct.code", "chunk_launch_exposed_ms.code",
                    "admit_exposed_ms.code")}


def test_program_spans_on_a_tiny_server(tmp_path):
    """Every span appears, each inside a tick, once per dispatch."""
    import jax
    from jax.sharding import Mesh

    from repro.configs.base import ParallelConfig, get_smoke_config
    from repro.models import model as M
    from repro.runtime.scheduler import ChunkScheduler
    from repro.runtime.server import Request, ServeConfig, Server
    cfg = get_smoke_config("minicpm_2b")
    par = ParallelConfig(tp=1, dp=1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    srv = Server(cfg, par, mesh, M.init_model(jax.random.PRNGKey(0), cfg,
                                              par),
                 ServeConfig(max_batch=2, max_seq=64, eos_token=-1,
                             max_new_tokens=3, block_size=8,
                             prefill_chunk=8))
    sched = ChunkScheduler(srv)
    for rid, n in enumerate((5, 13, 20)):
        sched.submit(Request(rid=rid, prompt=np.arange(n, dtype=np.int32)))
    jax.profiler.start_trace(str(tmp_path))
    ticks = 0
    try:
        while sched.has_work():
            sched.tick()
            ticks += 1
    finally:
        jax.profiler.stop_trace()
    got = spans.read_program(str(tmp_path))
    count = collections.Counter(n for n, _, _ in got)
    assert set(count) == set(SPANS)
    assert count["sched.tick"] == count["sched.admit"] == ticks
    assert count["serve.chunk.launch"] == srv.prefill_dispatches == 1 + 2 + 3
    assert count["serve.chunk.readback"] == count["serve.chunk.emit"] == 3
    for name in ("serve.decode.launch", "serve.decode.readback",
                 "serve.decode.emit"):
        assert count[name] == srv.decode_dispatches
    tick = [(s, e) for n, s, e in got if n == "sched.tick"]
    for n, s, e in got:
        if n != "sched.tick":
            assert any(ts <= s and e <= te for ts, te in tick), n


def _events(program=True):
    """Two devices, a traced window [0, 100) and the program's spans;
    times in ns.  Idle: TPU:0 [0,10) [20,30) [60,70) [80,100), TPU:1
    [50,100)."""
    ev = {"lines": {},
          "host": [("bench.window", 0, 100), ("bench.tick", 4, 96)],
          "devices": {
              "/device:TPU:0": {"modules": [],
                                "ops": [("fusion.1", 10, 20),
                                        ("fusion.2", 30, 60),
                                        ("copy.3", 70, 80)]},
              "/device:TPU:1": {"modules": [],
                                "ops": [("fusion.1", -10, 50)]}}}
    if program:
        ev["program"] = [
            ("sched.tick", 5, 65), ("sched.admit", 5, 12),
            ("serve.chunk.launch", 15, 25), ("serve.decode.launch", 28, 35),
            ("serve.decode.readback", 35, 62), ("sched.tick", 66, 95),
            ("serve.decode.launch", 66, 72), ("serve.decode.emit", 120, 130)]
    return ev


def _reduced(ev):
    return spans.extend(trace.reduce(ev), ev)


@pytest.mark.parametrize("names,ns", [
    # both ticks: TPU:0 5 + 10 + 5 and 4 + 15, TPU:1 15 and 29; per chip
    (["sched.tick"], (39 + 44) / 2),
    # a span that overlaps an idle interval in part: TPU:0 [20,25)
    (["serve.chunk.launch"], 5 / 2),
    # a span nested in another is not counted twice
    (["sched.tick", "sched.admit", "serve.decode.launch"], (39 + 44) / 2),
    # the readback: TPU:0 [60,62), TPU:1 [50,62)
    (["serve.decode.readback"], (2 + 12) / 2),
    # a span past the window counts nothing
    (["serve.decode.emit"], 0.0)])
def test_exposed_is_the_exact_overlap_averaged_over_the_chips(names, ns):
    assert spans.exposed_s(_reduced(_events()), names) == pytest.approx(
        ns * 1e-9)


def test_no_program_spans_give_nothing():
    r = _reduced(_events(program=False))
    assert r["program_spans"] == []
    assert spans.exposed_s(r, ["sched.tick"]) is None
    assert spans.attribute(r) == [] and spans.counts(r) == {}
    assert spans.exposed_s(None, ["sched.tick"]) is None


def test_idle_intervals_and_counts():
    r = _reduced(_events())
    assert r["idle"] == {"/device:TPU:0": [(0, 10), (20, 30), (60, 70),
                                           (80, 100)],
                         "/device:TPU:1": [(50, 100)]}
    assert spans.counts(r) == {"sched.tick": 2, "sched.admit": 1,
                               "serve.chunk.launch": 1,
                               "serve.decode.launch": 2,
                               "serve.decode.readback": 1}


def test_idle_goes_to_the_innermost_span():
    got = dict(spans.attribute(_reduced(_events())))
    want = {spans.OUTSIDE: (5 + 1 + 5 + 1 + 5) / 2,
            "sched.admit": 5 / 2, "serve.chunk.launch": 5 / 2,
            "sched.tick": (3 + 3 + 15 + 3 + 23) / 2,
            "serve.decode.launch": (2 + 4 + 6) / 2,
            "serve.decode.readback": (2 + 12) / 2}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # every idle nanosecond is named once: 50 per chip
    assert sum(got.values()) == pytest.approx(50e-9)


def _recorded(name):
    with open(os.path.join(HERE, "data", name + "_trace.json")) as f:
        ev = json.load(f)
    for key in ("host", "program"):
        if key in ev:
            ev[key] = [tuple(e) for e in ev[key]]
    return ev


def _read(metric, reduced, calls=(), window=None):
    mod = manifest.load_module(
        os.path.join(pbench_tiny.BENCH, "metrics", metric + ".py"), metric)
    return mod.read(layers.RunData(None, None, None, 1, list(calls), window,
                                   reduced))


@pytest.mark.parametrize("name", ["chat", "code"])
def test_recorded_traces_without_program_spans(name):
    """On the traces recorded before the program had spans, ``extend``
    leaves every key of ``reduce`` as it was, and the readers of the
    spans find nothing to read."""
    ev = _recorded(name)
    before = trace.reduce(ev)
    after = spans.extend(trace.reduce(ev), ev)
    assert {k: after[k] for k in before} == before
    assert after["program_spans"] == []
    for metric in READERS[name]:
        assert _read(metric, after) is None


def _calls(ev):
    """The benchmark's chunk records in a recorded window: a chunk is
    final when a ``serve.chunk.readback`` runs inside its span."""
    finals = [s for n, s, _ in ev["program"] if n == "serve.chunk.readback"]
    return [serving.Call("chunk", s / 1e9, e / 1e9,
                         (0, 0, any(s <= t < e for t in finals)), 0)
            for n, s, e in ev["host"] if n == "bench.prefill_chunk"]


def _span_s(reduced, names):
    return sum(e - s for s, e in trace.union(
        [(s, e) for n, s, e in reduced["program_spans"] if n in names])) / 1e9


@pytest.mark.parametrize("name", ["chat", "code"])
def test_recorded_traces_with_program_spans(name):
    """A few whole ticks recorded on a v5e with the program's spans: every
    reader gives a value, and no value exceeds the span it reads."""
    ev = _recorded(name + "_spans")
    r = _reduced(ev)
    lo, hi = next(sp[1:] for sp in ev["host"] if sp[0] == trace.WINDOW)
    calls = _calls(ev)
    got = {m: _read(m, r, calls, (lo / 1e9, hi / 1e9)) for m in READERS[name]}
    assert all(v is not None and v >= 0 for v in got.values()), got
    n = spans.counts(r)
    assert got["host_exposed_pct." + name] <= min(
        100 * _span_s(r, ["sched.tick"]) / r["window_s"],
        100 * (1 - r["busy_s"] / r["window_s"]))
    if name == "chat":
        per = n["serve.decode.launch"]
        assert got["decode_launch_exposed_ms.chat"] <= 1e3 * _span_s(
            r, ["serve.decode.launch"]) / per
        assert got["decode_emit_exposed_ms.chat"] <= 1e3 * _span_s(
            r, ["serve.decode.readback", "serve.decode.emit"]) / per
        # the decode phases sit inside the benchmark's decode span
        steps = trace.union([(s, e) for n_, s, e in ev["host"]
                             if n_ == "bench.decode_step"])
        idle_in_steps = sum(ns for iv in r["idle"].values()
                            for _, ns in spans._overlaps(iv, steps)
                            ) / len(r["idle"])
        assert (got["decode_launch_exposed_ms.chat"]
                + got["decode_emit_exposed_ms.chat"]) * per <= \
            idle_in_steps / 1e6 + 1e-9
    else:
        assert got["chunk_launch_exposed_ms.code"] <= 1e3 * _span_s(
            r, ["serve.chunk.launch"]) / n["serve.chunk.launch"]
        finals = sum(1 for c in calls if c.info[2])
        assert finals >= 1
        assert got["admit_exposed_ms.code"] <= 1e3 * _span_s(
            r, ["sched.admit"]) / finals
