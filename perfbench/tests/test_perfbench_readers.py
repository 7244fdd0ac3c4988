"""Every per-layer reader on the four windows recorded on a v5e, with the
same dispatch records each time, reads what it read before; a roofline
share is each chip's share of the work against that chip's device time;
and the collective share of a four-chip window."""
import json
import os

import pytest

import pbench_tiny
from pbench import harness, layers, manifest, serving, spans, trace, work

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(pbench_tiny.BENCH, "metrics")
#: each recorded window, with the configuration it served
TRACES = {"chat": "minicpm_2b", "chat_spans": "minicpm_2b",
          "code": "phi4_mini", "code_spans": "phi4_mini"}
#: a window recorded on four chips serving MiniCPM-2B at tp=4
TP4 = "tp4_serve"
PEAK = harness.load_peaks()["TPU v5 lite"]


def _recorded(name):
    with open(os.path.join(HERE, "data", name + "_trace.json")) as f:
        ev = json.load(f)
    for key in ("host", "program"):
        if key in ev:
            ev[key] = [tuple(e) for e in ev[key]]
    return ev


def _config(name):
    with open(os.path.join(pbench_tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _calls(ev, slots):
    """Dispatch records for the window's dispatch spans, the same for
    every run of the test: the k-th decode step holds ``slots`` slots at
    positions 256 + 29 j + 3 k, and every fifth chunk of 256 tokens is a
    final one of 131."""
    calls, k, i = [], 0, 0
    for n, s, e in sorted(ev["host"], key=lambda sp: sp[1]):
        if n == "bench.decode_step":
            info = tuple(256 + 29 * j + 3 * k for j in range(slots))
            calls.append(serving.Call("decode", s / 1e9, e / 1e9, info,
                                      slots))
            k += 1
        elif n == "bench.prefill_chunk":
            final = i % 5 == 4
            calls.append(serving.Call("chunk", s / 1e9, e / 1e9,
                                      (256 * (i % 5), 131 if final else 256,
                                       final), int(final)))
            i += 1
    return calls


def _steps(ev):
    """Training-step records for the training readers: one a tick."""
    return [type("Step", (), {"t0": s / 1e9, "t1": e / 1e9})()
            for n, s, e in ev["host"] if n == "bench.tick"]


def _run(name, chips=1):
    ev = _recorded(name)
    conf = _config(dict(TRACES, **{TP4: "minicpm_2b"})[name])
    reduced = spans.extend(trace.reduce(ev), ev)
    lo, hi = next(sp[1:] for sp in ev["host"] if sp[0] == trace.WINDOW)
    dims = work.Dims.of(conf["model"])
    calls = _calls(ev, conf["server"]["max_batch"])
    return layers.RunData(None, dims, PEAK, chips, calls,
                          (lo / 1e9, hi / 1e9), reduced), ev


def _train_run(name):
    ev = _recorded(name)
    dims = work.Dims.of(_config("minicpm_2b")["model"])
    lo, hi = next(sp[1:] for sp in ev["host"] if sp[0] == trace.WINDOW)
    cell = type("Cell", (), {"traffic": {"global_batch": 8, "seq_len": 256}})
    return layers.TrainData(cell, dims, PEAK, 4, _steps(ev),
                            (lo / 1e9, hi / 1e9),
                            spans.extend(trace.reduce(ev), ev))


def readings(name):
    """Every reader under ``metrics/`` on the recorded window ``name``."""
    run, _ = _run(name)
    train = _train_run(name)
    got = {}
    for f in sorted(os.listdir(METRICS)):
        metric = f[:-len(".py")]
        mod = manifest.load_module(os.path.join(METRICS, f), metric)
        got[metric] = mod.read(train if metric.endswith(".train") else run)
    return got


#: the readings of the tree before configurations could bring their own
#: work counts and rooflines became per chip
BEFORE = {'chat': {'admit_exposed_ms.code': None,
                   'ag_gemm_roofline_pct.train': None,
                   'chunk_launch_exposed_ms.code': None,
                   'decode_dispatch_ms.chat': 225.41298179999995,
                   'decode_emit_exposed_ms.chat': None,
                   'decode_hbm_pct.chat': 4.555473127931394,
                   'decode_launch_exposed_ms.chat': None,
                   'decode_step_ms.chat': 222.0320558,
                   'gemm_rs_roofline_pct.train': None,
                   'host_exposed_pct.chat': None,
                   'host_exposed_pct.code': None,
                   'idle_pct.chat': 81.41658057872885,
                   'idle_pct.code': 81.41658057872885,
                   'idle_pct.train': 81.41658057872885,
                   'mfu.chat': 0.20265140405790333,
                   'mfu.code': 0.20265140405790333,
                   'mfu.train': 19.00586575303475,
                   'prefill_chunk_ms.code': None,
                   'prefill_dispatch_ms.code': None,
                   'prefill_roofline_pct.code': None},
          'chat_spans': {'admit_exposed_ms.code': None,
                         'ag_gemm_roofline_pct.train': None,
                         'chunk_launch_exposed_ms.code': 2.045569,
                         'decode_dispatch_ms.chat': 225.60277749999997,
                         'decode_emit_exposed_ms.chat': 2.556254,
                         'decode_hbm_pct.chat': 4.541473198325475,
                         'decode_launch_exposed_ms.chat': 1.0272635,
                         'decode_step_ms.chat': 222.0029115,
                         'gemm_rs_roofline_pct.train': None,
                         'host_exposed_pct.chat': 2.173937918602619,
                         'host_exposed_pct.code': 2.173937918602619,
                         'idle_pct.chat': 2.1832475703255527,
                         'idle_pct.code': 2.1832475703255527,
                         'idle_pct.train': 2.1832475703255527,
                         'mfu.chat': 1.3035340479359123,
                         'mfu.code': 1.3035340479359123,
                         'mfu.train': 15.260770339154181,
                         'prefill_chunk_ms.code': 105.40965299999999,
                         'prefill_dispatch_ms.code': 109.939971,
                         'prefill_roofline_pct.code': 6.079086272084227},
          'code': {'admit_exposed_ms.code': None,
                   'ag_gemm_roofline_pct.train': None,
                   'chunk_launch_exposed_ms.code': None,
                   'decode_dispatch_ms.chat': 52.561548894736845,
                   'decode_emit_exposed_ms.chat': None,
                   'decode_hbm_pct.chat': 33.40948372192175,
                   'decode_launch_exposed_ms.chat': None,
                   'decode_step_ms.chat': 29.515975421052634,
                   'gemm_rs_roofline_pct.train': None,
                   'host_exposed_pct.chat': None,
                   'host_exposed_pct.code': None,
                   'idle_pct.chat': 98.04859798340554,
                   'idle_pct.code': 98.04859798340554,
                   'idle_pct.train': 98.04859798340554,
                   'mfu.chat': 11.863452329771867,
                   'mfu.code': 11.863452329771867,
                   'mfu.train': 68.81938616356575,
                   'prefill_chunk_ms.code': 31.032186176470585,
                   'prefill_dispatch_ms.code': 10.60352929411765,
                   'prefill_roofline_pct.code': 28.29486343436836},
          'code_spans': {'admit_exposed_ms.code': None,
                         'ag_gemm_roofline_pct.train': None,
                         'chunk_launch_exposed_ms.code': 2.4105543333333332,
                         'decode_dispatch_ms.chat': 51.722382333333314,
                         'decode_emit_exposed_ms.chat': 1.5093443333333332,
                         'decode_hbm_pct.chat': 33.45224913264773,
                         'decode_launch_exposed_ms.chat': 0.45287499999999997,
                         'decode_step_ms.chat': 29.38638733333333,
                         'gemm_rs_roofline_pct.train': None,
                         'host_exposed_pct.chat': 7.881750541844266,
                         'host_exposed_pct.code': 7.881750541844266,
                         'idle_pct.chat': 7.900638835424134,
                         'idle_pct.code': 7.900638835424134,
                         'idle_pct.train': 7.900638835424134,
                         'mfu.chat': 13.546061494292926,
                         'mfu.code': 13.546061494292926,
                         'mfu.train': 65.33608184361381,
                         'prefill_chunk_ms.code': 31.030051666666665,
                         'prefill_dispatch_ms.code': 13.778949333333328,
                         'prefill_roofline_pct.code': 27.613193526701995}}


@pytest.mark.parametrize("name", sorted(TRACES))
def test_every_reader_reads_what_it_read_before(name):
    got = readings(name)
    assert set(BEFORE[name]) <= set(got)
    assert {m: got[m] for m in BEFORE[name]} == BEFORE[name]


@pytest.mark.parametrize("name,kind,bound", [
    ("chat", "decode", "bytes"), ("chat_spans", "decode", "bytes"),
    ("code", "chunk", "max"), ("code_spans", "chunk", "max")])
def test_a_roofline_is_each_chips_share_of_the_work(name, kind, bound):
    """At one chip the share is the whole step's; at four, each chip does
    a quarter of the work in the first chip's device time."""
    one, _ = _run(name)
    four, _ = _run(name, chips=4)
    got = layers.roofline_pct(one, kind, bound)
    metric = {"decode": "decode_hbm_pct.chat",
              "chunk": "prefill_roofline_pct.code"}[kind]
    assert got == BEFORE[name][metric]
    assert layers.roofline_pct(four, kind, bound) == pytest.approx(
        got / 4, rel=1e-12)


def _hand_made():
    """Two devices and a window [0, 100) ns: on TPU:0 an asynchronous
    all-gather from 10 to 45 with compute over [12, 30), and an
    all-reduce over [50, 60) half covered by compute; TPU:1 computes
    only."""
    return {"lines": {}, "host": [("bench.window", 0, 100)],
            "devices": {
                "/device:TPU:0": {"modules": [], "ops": [
                    ("%all-gather-start.1 = (bf16[8], bf16[32]) "
                     "all-gather-start(%p)", 10, 12),
                    ("%fusion.1 = bf16[8] fusion(%all-gather-start.1)",
                     12, 30),
                    ("%all-gather-done.1 = bf16[32] all-gather-done(%a)",
                     40, 45),
                    ("%all-reduce.2 = f32[8] all-reduce(%x)", 50, 60),
                    ("%fusion.2 = f32[8] fusion(%y)", 55, 70)]},
                "/device:TPU:1": {"modules": [], "ops": [
                    ("%fusion.3 = f32[8] fusion(%all-reduce.9)", 0, 10)]}}}


def test_collective_share_on_a_hand_made_window():
    run = layers.RunData(None, None, PEAK, 2, [], (0, 1e-7),
                         trace.reduce(_hand_made()))
    # TPU:0: [10, 45) and [50, 60), 45 ns; none on TPU:1; per device
    assert layers.collective_pct(run) == pytest.approx(22.5)
    # exposed: [10, 12) [30, 45) and [50, 55), 22 ns
    assert layers.collective_pct(run, exposed=True) == pytest.approx(11.0)


def test_collective_ops_are_named_by_their_instruction():
    assert trace.collective("%all-reduce.3 = f32[8] all-reduce(%x)") == (
        "all-reduce", "")
    assert trace.collective("%collective-permute-done.2 bf16[4,8] "
                            "collective-permute-done") == (
        "collective-permute", "done")
    assert trace.collective("%reduce-scatter.1 f32[2] reduce-scatter") == (
        "reduce-scatter", "")
    # XLA names an op after the JAX primitive: the opcode tells
    assert trace.collective(
        "%psum.152 = bf16[16,1,2304]{2,0,1:T(8,128)(2,1)S(1)} all-reduce("
        "bf16[16,1,2304]{2,0,1:T(8,128)(2,1)S(1)} %fusion.180), "
        "channel_id=4, replica_groups={{0,1,2,3}}") == ("all-reduce", "")
    assert trace.collective("%psum.152 bf16[16,1,2304] all-reduce") == (
        "all-reduce", "")
    assert trace.collective("%all-gather-start.1 (bf16[8] bf16") == (
        "all-gather", "start")
    for other in ("%fusion.1 = f32[8] fusion(%all-reduce.2)",
                  "%iota_reduce_fusion.1 = (bf16[16]{0}, s32[16]{0}) "
                  "fusion(bf16[4,1,16]{2,1,0} %all-gather), kind=kLoop",
                  "%all-reduce-scatter-fusion.1 = f32[8] fusion(%x)",
                  "%copy.3 f32[8] copy"):
        assert trace.collective(other) is None


@pytest.mark.parametrize("name", sorted(TRACES))
def test_a_one_chip_window_holds_no_collective(name):
    run, _ = _run(name)
    assert layers.collective_pct(run) is None
    assert layers.collective_pct(run, exposed=True) is None


def test_a_four_chip_window_reads_its_collectives():
    """A window recorded on four v5e chips serving MiniCPM-2B at tp=4:
    each decode layer's two all-reduces are collectives on every chip,
    and the exposed part of their time is at most the whole."""
    ev = _recorded(TP4)
    r = trace.reduce(ev)
    assert len(r["leaf_ops"]) == 4
    for ops in r["leaf_ops"].values():
        kinds = {trace.collective(n) for n, _, _ in ops} - {None}
        assert ("all-reduce", "") in kinds
    run = layers.RunData(None, None, PEAK, 4, [], None, r)
    total = layers.collective_pct(run)
    exposed = layers.collective_pct(run, exposed=True)
    assert total is not None and 0 < exposed <= total < 100


def test_four_chip_roofline_shares_are_per_chip():
    """On the four-chip window each share is the chips' own: under 100%,
    and a quarter of what the whole step's work against one chip's time
    would read."""
    four, _ = _run(TP4, chips=4)
    one, _ = _run(TP4)
    for kind, bound in (("decode", "bytes"), ("chunk", "max")):
        got = layers.roofline_pct(four, kind, bound)
        assert 0 < got <= 100
        assert got == pytest.approx(layers.roofline_pct(one, kind, bound) / 4,
                                    rel=1e-12)
    assert 0 < layers.mfu_pct(four) <= 100
