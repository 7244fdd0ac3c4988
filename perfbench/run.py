"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 perfbench/run.py --workload minicpm_2b.chat --seed 7 \\
        --seconds 30 --trace 0

Sets up the cell's server (random weights from ``--seed``, both programs
compiled and warmed) or trainer (state from ``--seed``, driven through its
checked steps), drives it with the cell's traffic or batches for
``--seconds``, checks what it served or learned against the
configuration's plain reference, and prints one JSON object as the last
line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``, which also adds ``breakdown``), ``device``
and, last, ``checks``: each compared number with its limit.  The same
numbers end standard error.

It exits non-zero and prints no result without an accelerator, with fewer
chips than the cell asks for, or without the program next to it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # limit-setting only: put the float8 control in the program's place in
    # the comparison (the benchmark's own runs never pass it)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    # limit-setting only: plant a fault in a training cell's program
    ap.add_argument("--fault", default=None)
    # a copy of the trace's events, cut short, for the reduction's tests
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)

    from pbench import harness, manifest
    try:
        cell = manifest.load_cell(args.workload, ROOT)
    except manifest.ManifestError as e:
        _err(f"run.py: {e}")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _err(f"run.py: the program (src/repro) is not in {ROOT}")
        return 2
    sys.path.insert(0, src)

    import jax
    try:
        devices = harness.find_devices(cell.chips, harness.load_peaks())
    except harness.NoChip as e:
        _err(f"run.py: {e}")
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.cache import enable_compilation_cache
    _err(f"compilation cache: {enable_compilation_cache()}")
    peak = harness.load_peaks()[devices[0].device_kind]
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, peak,
                              t_start=T_START, control=bool(args.control),
                              log=_err, dump=args.dump, fault=args.fault)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
