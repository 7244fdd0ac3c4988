"""Readings for a training cell's limits, in one process on its chips.

    python3 perfbench/limits.py --workload minicpm_2b.train_sp_flux \\
        --seeds 11,12,13 --control 11,12,13 \\
        --faults half_batch,no_exchange --fault-seeds 11,12,13

For each seed the program is built and driven through its checked steps
exactly as a run's set-up does (``training.build``) and compared with the
float32 reference; with ``--control`` the float8 reference is compared in
its place, and with ``--faults`` the program with each fault planted.  One
JSON line per reading on standard output.  The benchmark's own runs never
call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)

    from pbench import harness, manifest, training
    cell = manifest.load_cell(args.workload, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    devices = harness.find_devices(cell.chips, harness.load_peaks())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.cache import enable_compilation_cache
    enable_compilation_cache()
    control = set(_seeds(args.control))
    fault_seeds = set(_seeds(args.fault_seeds))
    faults = [f for f in args.faults.split(",") if f]

    def emit(seed, kind, got, t, loss):
        print(json.dumps({"seed": seed, "kind": kind, "seconds": t, **got,
                          "loss": loss}), flush=True)

    for seed in _seeds(args.seeds):
        t = time.perf_counter()
        run = training.build(cell, seed)
        readings = run.readings
        training.free(run)
        t_build = time.perf_counter() - t
        t = time.perf_counter()
        ref = training.reference(cell, seed, devices)
        emit(seed, "reference", {}, time.perf_counter() - t, ref["loss"])
        emit(seed, "program", training.compare(readings, ref), t_build,
             readings["loss"])
        for name in ("grad", "change"):
            r = training.as_leaves(ref, readings)[name]
            print(json.dumps({"seed": seed, "leaves": name, "program":
                              readings[name], "reference": r}),
                  file=sys.stderr, flush=True)
        if seed in control:
            t = time.perf_counter()
            ctl = training.reference(cell, seed, devices, fp8=True)
            emit(seed, "control", training.compare(
                training.as_leaves(ctl, readings), ref),
                time.perf_counter() - t, ctl["loss"])
            for name in ("grad", "change"):
                print(json.dumps({"seed": seed, "leaves": name, "control":
                                  training.as_leaves(ctl, readings)[name]}),
                      file=sys.stderr, flush=True)
        if seed in fault_seeds:
            for fault in faults:
                t = time.perf_counter()
                run = training.build(cell, seed, fault)
                got = run.readings
                training.free(run)
                emit(seed, fault, training.compare(got, ref),
                     time.perf_counter() - t, got["loss"])
    for i, dev in enumerate(devices):
        stats = dev.memory_stats() or {}
        print(f"device {i} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
