"""The trace reduction, on small recorded and hand-made traces."""
import json
import os

import pytest

import pbench_tiny  # noqa: F401  (puts the benchmark on the path)
from pbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _events():
    """Two devices, a traced window [0, 100) and host spans around the
    dispatches; times in ns."""
    return {
        "lines": {},
        "host": [("bench.window", 0, 100), ("bench.tick", 6, 60),
                 ("bench.prefill_chunk", 8, 20),
                 ("bench.decode_step", 30, 58), ("bench.tick", 60, 98)],
        "devices": {
            "/device:TPU:0": {
                "modules": [("jit_fn", 10, 25), ("jit_fn", 35, 55),
                            ("jit_fn", 70, 80)],
                "ops": [("fusion.1", 10, 20), ("fusion.2", 18, 25),
                        ("fusion.1", 35, 55), ("copy.3", 70, 80),
                        ("fusion.1", 120, 130)]},
            "/device:TPU:1": {"modules": [("jit_fn", 0, 50)],
                              "ops": [("fusion.1", -10, 50)]},
        },
    }


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 12)]) == [(0, 4),
                                                               (5, 12)]


def test_busy_is_the_union_of_ops_inside_the_window():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_by_device"]["/device:TPU:0"] == pytest.approx(45e-9)
    # the op that starts before the window is clipped to it
    assert r["busy_by_device"]["/device:TPU:1"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(47.5e-9)


def test_op_time_by_name():
    ops = dict(trace.reduce(_events())["device_ops"])
    # fusion.1: 10 + 20 on TPU:0 (the one past the window dropped) + 50 on
    # TPU:1, per chip of two
    assert ops["fusion.1"] == pytest.approx(40e-9)
    assert ops["copy.3"] == pytest.approx(5e-9)


def test_idle_gaps_named_by_the_innermost_host_span():
    gaps = dict(trace.reduce(_events())["idle_gaps"])
    # TPU:0 gaps [0,10) outside any span but the window, [25,35) in
    # tick/decode_step (midpoint 30 is its start), [55,70) mid 62 in the
    # second tick, [80,100) mid 90 in the second tick; TPU:1 [50,100)
    # mid 75; each averaged over the two devices
    assert gaps[trace.OUTSIDE] == pytest.approx(5e-9)
    assert gaps["bench.decode_step"] == pytest.approx(5e-9)
    assert gaps["bench.tick"] == pytest.approx((15 + 20 + 50) / 2 * 1e-9)


def test_program_times_go_to_the_dispatch_span_before_them():
    r = trace.reduce(_events())
    # spans: prefill_chunk at 8, decode_step at 30; the module at 70 runs
    # after the decode's span started and no later span, so it is the
    # decode's too
    times = trace.program_times(r, ["chunk", "decode"])
    assert times["chunk"] == [pytest.approx(15e-9)]
    assert times["decode"] == [pytest.approx(30e-9)]
    # a count that does not match the dispatches gives nothing
    assert trace.program_times(r, ["decode"]) == {}


def test_no_window_or_no_device_work_is_an_error():
    ev = _events()
    ev["host"] = [sp for sp in ev["host"] if sp[0] != trace.WINDOW]
    with pytest.raises(RuntimeError):
        trace.reduce(ev)
    ev = _events()
    ev["devices"] = {}
    with pytest.raises(RuntimeError):
        trace.reduce(ev)


def _recorded(name):
    with open(os.path.join(HERE, "data", name + "_trace.json")) as f:
        ev = json.load(f)
    ev["host"] = [tuple(e) for e in ev["host"]]
    return ev


@pytest.mark.parametrize("name,programs", [
    ("chat", {"decode": 5}), ("code", {"chunk": 17, "decode": 19})])
def test_recorded_trace_reduces(name, programs):
    """A short window recorded on a v5e: every program execution falls
    to a dispatch span, and the programs' device time sits inside the
    device's busy time."""
    r = trace.reduce(_recorded(name))
    assert 0 < r["busy_s"] <= r["window_s"]
    kinds = ["decode" if sp[0] == "bench.decode_step" else "chunk"
             for sp in r["dispatch_spans"]]
    times = trace.program_times(r, kinds)
    assert {k: len(v) for k, v in times.items()} == programs
    assert all(t > 0 for v in times.values() for t in v)
    # gaps are named by the span the host was in
    assert {n for n, _ in r["idle_gaps"]} <= {
        trace.OUTSIDE, "bench.tick", "bench.admit", "bench.decode_step",
        "bench.prefill_chunk"}


def test_op_labels_keep_name_type_and_opcode():
    assert trace.op_label(
        "%convert.75 = f32[1024,16,36,64]{3,2,1,0:T(8,128)} "
        "convert(bf16[1024,16,36,64]{3,2,1,0} %fusion.143)") == \
        "%convert.75 f32[1024,16,36,64] convert"
    assert trace.op_label("custom op") == "custom op"


def test_enclosing_ops_are_not_counted_twice():
    ev = _events()
    ev["devices"]["/device:TPU:0"]["ops"] = [
        ("while.1", 10, 50), ("fusion.1", 10, 20), ("fusion.2", 30, 50)]
    ops = dict(trace.reduce(ev)["device_ops"])
    assert "while.1" not in ops
    # 10 on TPU:0 and 50 on TPU:1, per chip of two
    assert ops["fusion.1"] == pytest.approx((10e-9 + 50e-9) / 2)


#: seam kernel calls as the TPU compiler prints them (a flux training step
#: at tp=4: 512 rows a chip), and ops that are not seam kernels
AG = ("%closed_call.282 = (bf16[2048,1792]{1,0:T(8,128)(2,1)S(1)}, "
      "bf16[4,512,2304]{2,1,0:T(8,128)(2,1)}) custom-call(%multiply_bitcast"
      "_fusion.6, %pad.201), custom_call_target=\"tpu_custom_call\"")
RS = ("%transpose_jvp___.1 = (bf16[512,2304]{1,0:T(8,128)(2,1)S(1)}, "
      "bf16[4,512,2304]{2,1,0:T(8,128)(2,1)}) custom-call(%fusion.179, "
      "%param.39), custom_call_target=\"tpu_custom_call\"")
OTHER = ["%fusion.249 = s32[1,2,8,128]{3,2,1,0} fusion(%p), kind=kLoop",
         "%custom-call.62 = bf16[2304,640]{1,0} custom-call(%p), "
         "custom_call_target=\"tpu_custom_call\"",
         "%tuple_fusion = (bf16[2048,1792]{1,0}, bf16[4,512,2304]{2,1,0}) "
         "fusion(%a, %b), kind=kLoop"]


def test_seam_kernels_are_named_by_their_result_types():
    assert trace.seam_kernel(AG) == "ag_gemm"
    assert trace.seam_kernel(RS) == "gemm_rs"
    assert [trace.seam_kernel(n) for n in OTHER] == [None] * len(OTHER)
    assert trace.op_label(AG) == "%closed_call.282 ag_gemm"


def test_kernel_times_on_the_first_device():
    ev = {"lines": {}, "host": [("bench.window", 0, 100)],
          "devices": {"/device:TPU:0": {"modules": [("jit_step_fn", 0, 90)],
                                        "ops": [(AG, 0, 10), (RS, 10, 30),
                                                (AG, 30, 35),
                                                (OTHER[0], 35, 40)]},
                      "/device:TPU:1": {"modules": [], "ops": [(AG, 0, 50)]}}}
    got = trace.kernel_times(trace.reduce(ev))
    assert got == {"ag_gemm": [10e-9, 5e-9], "gemm_rs": [20e-9]}


def test_kernel_roofline_reads_only_whole_steps():
    """The least time of the calls the traced steps make over the kernel's
    device time; nothing to read when the trace holds another number of
    calls than those steps make."""
    from pbench import layers, work
    with open(os.path.join(pbench_tiny.BENCH, "configs",
                           "minicpm_2b.json")) as f:
        conf = json.load(f)
    dims = work.Dims.of(conf["model"])
    cell = type("Cell", (), {"traffic": {"global_batch": 8, "seq_len": 256}})
    calls = work.seam_calls(dims, 8, 256, 4)["ag_gemm"]
    step = type("Step", (), {"t0": 1.0, "t1": 2.0})
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = [(AG, 10 * i, 10 * i + 2000) for i in range(len(calls))]
    reduced = {"leaf_ops": {"/device:TPU:0": ops}}
    run = layers.TrainData(cell, dims, peak, 4, [step], (0.0, 3.0), reduced)
    least = sum(w.least_s(197e12, 819e9) for w in calls)
    assert layers.kernel_roofline_pct(run, "ag_gemm") == pytest.approx(
        100 * least / (len(calls) * 2e-6))
    reduced["leaf_ops"]["/device:TPU:0"] = ops[:-1]
    assert layers.kernel_roofline_pct(run, "ag_gemm") is None
    assert layers.kernel_roofline_pct(run, "gemm_rs") is None
