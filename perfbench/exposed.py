"""Split a serving cell's device idle time by the program's own host spans.
One process on the cell's chips, one traced run per seed.

    python3 perfbench/exposed.py --workload phi4_mini.code \\
        --seeds 11,12,13 --seconds 51 \\
        --dump perfbench/tests/data/code_spans_trace.json

Each seed's run is what ``run.py --trace 1`` makes, without the
correctness check: the same server, traffic and window, traced for 8 s in
its middle.  ``trace.reduce`` and the harness read no program span, so this
is how the readers that need them run (PERF.md, section 7): every reader
``metrics/*.<traffic>.py`` reads the reduced trace extended with the
program's spans (``pbench/spans.py``).  One JSON line per seed on standard
output: those readings, the span counts, and the idle time by the
innermost program span beside the benchmark's own ``bench.*`` spans.  On a
program without the spans the readers that need them give null.
``--dump`` writes a few whole ticks of the first seed's trace for the
tests (``spans.dump``).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from pbench import harness, layers, manifest, serving, spans, trace
    cell = manifest.load_cell(args.workload, ROOT)
    devices = harness.find_devices(cell.chips, harness.load_peaks())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.cache import enable_compilation_cache
    enable_compilation_cache()
    peak = harness.load_peaks()[devices[0].device_kind]
    suffix = cell.name.split(".", 1)[1]
    readers = {}
    for path in sorted(glob.glob(os.path.join(cell.bench_dir, "metrics",
                                              f"*.{suffix}.py"))):
        name = os.path.basename(path)[:-3]
        readers[name] = manifest.load_module(path, name)
    counts = manifest.load_counts(cell)
    dims = counts.Dims.of(cell.config["model"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        server = serving.build_server(cell, seed)
        rec = serving.Recorder()
        rec.wrap(server)
        tdir = tempfile.mkdtemp(prefix="pbench_spans_")
        try:
            out = serving.run(server, rec, cell.traffic, seed, args.seconds,
                              tdir, min(harness.TRACE_S, args.seconds))
            events = trace.read_trace(tdir)
            events["program"] = spans.read_program(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        serving.free(server)
        del server
        reduced = spans.extend(trace.reduce(events), events)
        run = layers.RunData(cell, dims, peak, len(devices), rec.calls,
                             (out.tracer.t0, out.tracer.t1), reduced, counts)
        by_program = spans.attribute(reduced)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "metrics": {n: m.read(run) for n, m in readers.items()},
            "counts": spans.counts(reduced),
            "idle_by_program_span": by_program,
            "idle_by_bench_span": reduced["idle_gaps"],
            "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
            "device": devices[0].device_kind}), flush=True)
        print(f"seed {seed}: idle by program span "
              f"{[(n, round(s, 4)) for n, s in by_program]}",
              file=sys.stderr, flush=True)
        if args.dump and i == 0:
            spans.dump(events, args.dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
