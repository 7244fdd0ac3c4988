"""One run of one cell: set-up, the measured window, the check, the line."""
from __future__ import annotations

import json
import math
import os
import shutil
import tempfile
import time
from typing import Dict, Optional

from pbench import check, layers, serving, trace, training, work

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")
#: the traced sub-window in a ``--trace 1`` run, at most this long
TRACE_S = 8.0


class NoChip(Exception):
    pass


def load_peaks(path: str = PEAKS) -> Dict:
    with open(path) as f:
        return json.load(f)["devices"]


def find_devices(chips: int, peaks: Dict):
    """The cell's devices; an error without an accelerator, with fewer
    chips than the cell asks for, or for a device not in the peak table."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"no peaks for device kind {devs[0].device_kind!r} in "
                     f"peaks.json")
    return devs[:chips]


def _peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell, seed: int, seconds: float, traced: bool, devices,
             peak: Dict, t_start: Optional[float] = None,
             control: bool = False, log=print,
             dump: Optional[str] = None, fault: Optional[str] = None
             ) -> Dict:
    """Run ``cell`` once and return the result line's object.  ``log``
    takes the lines for standard error.  ``control`` and ``fault`` are for
    setting limits and for the tests only."""
    t_start = time.perf_counter() if t_start is None else t_start
    train = cell.traffic["loop"] == "train"
    if train:
        state = training.build(cell, seed, fault)
        rec = state
    else:
        if fault is not None:
            raise ValueError("faults are planted in training cells only")
        state = serving.build_server(cell, seed)
        rec = serving.Recorder()
        rec.wrap(state)
    setup_s = time.perf_counter() - t_start
    tdir = tempfile.mkdtemp(prefix="pbench_trace_") if traced else None
    try:
        if train:
            out = training.run_window(state, seconds, tdir,
                                      min(TRACE_S, seconds))
        else:
            out = serving.run(state, rec, cell.traffic, seed, seconds, tdir,
                              min(TRACE_S, seconds))
        mem = _peak_memory(devices)
        events = trace.read_trace(tdir) if traced else None
        reduced = trace.reduce(events) if traced else None
        if dump and traced:
            trace.dump(events, dump)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    if train:
        readings, steps = state.readings, state.steps
        training.free(state)
        e2e = {"train_tok_s": out.tokens / seconds}
        log(f"window {seconds}s: {len(out.counted)} steps completed, "
            f"{out.failed} failed, {out.tokens} tokens; compiles in the "
            f"window {out.compiles}; setup {setup_s:.2f}s")
    else:
        serving.free(state)
        e2e = serving.end_to_end(out, rec, seconds)
        log(f"window {seconds}s: {len(out.counted)} requests counted, "
            f"{len(out.finished)} finished, {out.failed} failed, "
            f"{out.tokens} output tokens; samples for the tails: "
            f"{e2e['_samples']}; ttft p50 {e2e['_ttft_p50_ms']:.1f} ms, "
            f"tpot p50 {e2e['_tpot_p50_ms']:.1f} ms; worst submission "
            f"delay {out.lateness_s * 1e3:.1f} ms; compiles in the window "
            f"{out.compiles}; setup {setup_s:.2f}s")
    del state
    e2e["setup_s"] = setup_s

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result: Dict = {"correct": False, "attempted": len(out.counted),
                    "failed": out.failed}
    if traced:
        dims = work.Dims.of(cell.config["model"])
        window = (out.tracer.t0, out.tracer.t1)
        if train:
            run = layers.TrainData(cell, dims, peak, len(devices), steps,
                                   window, reduced)
        else:
            run = layers.RunData(cell, dims, peak, len(devices), rec.calls,
                                 window, reduced)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced["device_ops"]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"]]}
        first = sorted(reduced["modules"])[0]
        names: Dict[str, int] = {}
        for n, _, _ in reduced["modules"][first]:
            names[n] = names.get(n, 0) + 1
        log(f"trace: busy {reduced['busy_s']:.4f}s of "
            f"{reduced['window_s']:.4f}s; {first} lines "
            f"{events['lines'].get(first)}; modules {names}; kernels "
            f"{ {k: (len(v), sum(v)) for k, v in run.kernel_times().items()} }")
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device

    if train:
        ok, checks, info = training.verdict(cell, readings, seed, devices,
                                            out.failed, control)
    else:
        ok, checks, info = check.verdict(cell, out, seed, control)
    log(f"check: {json.dumps(info)}")
    finite = all(isinstance(m["value"], (int, float))
                 and math.isfinite(m["value"]) for m in metrics.values())
    result["correct"] = bool(ok and finite)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
