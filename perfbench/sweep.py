"""Find an open-loop cell's knee: the highest arrival rate the server
sustains without a growing backlog.  One process, one server, the rates in
turn; run once when a cell is defined, on the chip.

    python3 perfbench/sweep.py --workload minicpm_2b.chat --seed 5 \\
        --seconds 40 --rates 0.3,0.45,0.6,0.75,0.9

For each rate it prints one JSON line: requests counted and finished, the
TTFT median and 90th percentile, the TPOT 90th percentile, and the TTFT
median of the window's first and last thirds of arrivals (a backlog that
grows shows as a last third far above the first).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain-cap", type=float, default=None,
                    help="seconds a rate may take to drain after its window")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from pbench import harness, manifest, serving
    from pbench import traffic as T
    cell = manifest.load_cell(args.workload, ROOT)
    harness.find_devices(cell.chips, harness.load_peaks())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.cache import enable_compilation_cache
    enable_compilation_cache()
    server = None
    for rate in [float(r) for r in args.rates.split(",")]:
        server = (serving.build_server(cell, args.seed) if server is None
                  else serving.fresh(cell, server))
        mix = dict(cell.traffic, rate_rps=rate)
        if args.drain_cap is not None:
            mix["drain_cap_s"] = args.drain_cap
        rec = serving.Recorder()
        rec.wrap(server)
        out = serving.run(server, rec, mix, args.seed, args.seconds)
        reqs = sorted(out.finished, key=lambda r: rec.arrival[r.rid])
        third = max(1, len(reqs) // 3)
        ttft = lambda rs: T.percentile([rec.ttft_s(r.rid) for r in rs], 50)
        e2e = serving.end_to_end(out, rec, args.seconds)
        print(json.dumps({
            "rate_rps": rate, "counted": len(out.counted),
            "finished": len(out.finished), "failed": out.failed,
            "ttft_p50_ms": e2e["_ttft_p50_ms"],
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "tpot_p90_ms": e2e["tpot_p90_ms"],
            "ttft_first_third_ms": 1e3 * ttft(reqs[:third]),
            "ttft_last_third_ms": 1e3 * ttft(reqs[-third:]),
            "decode_steps": sum(c.kind == "decode" for c in rec.calls),
            "chunks": sum(c.kind == "chunk" for c in rec.calls)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
