"""Open-loop serving benchmark — the workload FLUX compares against vLLM.

A Poisson traffic generator submits mixed-length prompts at a fixed arrival
rate (open loop: arrivals don't wait for completions, so queueing delay is
REAL and counts against TTFT) into the paged continuous-batching runtime.
The chunk scheduler interleaves prefill chunks with decode steps, driving
the fused decode-AR seam (every decode) and the prefill AG/RS seams (every
chunk) per overlap mode.

Reported per mode, against an SLO:

* **TTFT** (time to first token, includes queueing) — mean/p50/p95/p99 +
  SLO attainment;
* **per-token latency** (TPOT: inter-token mean after the first token) —
  mean/p50/p95/p99;
* throughput (tokens/s), dispatch counts, and paged-pool stats
  (peak blocks in use vs the dense-cache equivalent, prefix-reuse hits /
  reused tokens / evictions).

The timed run repeats the warmup's prompts, so full prompt blocks
registered during warmup are reusable — warm-cache behavior, reported via
the reuse counter deltas.

CSV: name,us_per_call,derived  (us_per_call = us per generated token;
derived = tokens/s).  Writes ``experiments/BENCH_serving.json``.

At ``--tp 1`` (the CI default) every seam takes the single-shard fallback,
so the mode rows are transport-EQUIVALENT: they gate numerics
(``outputs_match_reference``) and give a serving-loop baseline, not a seam
comparison.  Run with ``--tp > 1`` (real TPU, or
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU) to actually
time the decode-AR / prefill AG-RS transports against each other.

    PYTHONPATH=src python benchmarks/serving.py --smoke
    PYTHONPATH=src python benchmarks/serving.py --num-requests 16 \\
        --arrival-rate 4
"""
from __future__ import annotations

import argparse
import json
import os
import time

MODES = ("decomposed", "xla")
OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "experiments", "BENCH_serving.json")


def _requests(cfg, n_requests, max_prompt, rng):
    import numpy as np
    from repro.runtime.server import Request
    lens = rng.integers(3, max_prompt + 1, size=n_requests)
    return [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=(int(n),)).astype(np.int32))
        for i, n in enumerate(lens)]


def _poisson_arrivals(n, rate_rps, rng):
    """Open-loop arrival offsets (seconds from t0): exponential gaps at
    ``rate_rps`` requests/s.  rate <= 0 means all requests arrive at t0
    (closed-batch limit)."""
    import numpy as np
    if rate_rps <= 0:
        return np.zeros(n)
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))


def open_loop_serve(server, reqs, offsets):
    """Drive the chunk scheduler with scheduled arrivals.  A request is
    submitted only once its offset elapses — TTFT therefore includes any
    queueing delay behind slower admissions (the open-loop property that
    closed-loop benchmarks hide)."""
    from repro.runtime.scheduler import ChunkScheduler
    sched = ChunkScheduler(server)
    done = []
    nxt = 0
    t0 = time.perf_counter()
    while nxt < len(reqs) or sched.has_work():
        now = time.perf_counter() - t0
        while nxt < len(reqs) and offsets[nxt] <= now:
            reqs[nxt].t_arrival = t0 + offsets[nxt]   # scheduled, not actual
            sched.submit(reqs[nxt])
            nxt += 1
        if not sched.has_work():
            if nxt < len(reqs):                       # idle until next arrival
                time.sleep(min(offsets[nxt] - now, 0.01))
            continue
        done.extend(sched.tick())
    wall = time.perf_counter() - t0
    return done, wall


def _stats(xs):
    import numpy as np
    xs = np.asarray(xs, np.float64)
    return {"mean": float(xs.mean()),
            "p50": float(np.percentile(xs, 50)),
            "p95": float(np.percentile(xs, 95)),
            "p99": float(np.percentile(xs, 99))}


def bench_mode(mode, cfg, params, mesh, sc, reqs_factory, offsets, tp,
               slo_ttft_s, wire_dtype=None):
    from repro.configs.base import ParallelConfig
    from repro.runtime.server import Server

    par = ParallelConfig(tp=tp, dp=1, overlap_mode=mode,
                         wire_dtype=wire_dtype)
    server = Server(cfg, par, mesh, params, sc)
    server.serve(reqs_factory())       # warmup: compiles + registers prefixes
    d0, p0 = server.decode_dispatches, server.prefill_dispatches
    pool = server.pool
    r0 = (pool.reuse_hits, pool.reused_tokens, pool.evictions)

    reqs = reqs_factory()              # same prompts: warm prefix cache
    done, wall = open_loop_serve(server, reqs, offsets)
    ok = [r for r in done if r.error is None]
    new_tokens = sum(len(r.output) for r in done)
    ttfts = [r.ttft_s() for r in ok]
    tpots = [r.per_token_s() for r in ok]
    return {
        "mode": mode,
        "wire_dtype": wire_dtype,
        "tokens_per_s": new_tokens / wall,
        "wall_s": wall,
        "new_tokens": new_tokens,
        "requests": len(done),
        "rejected": len(done) - len(ok),
        "decode_steps": server.decode_dispatches - d0,
        "prefill_dispatches": server.prefill_dispatches - p0,
        "ttft_s": _stats(ttfts),
        "per_token_s": _stats(tpots),
        "slo": {"ttft_s": slo_ttft_s,
                "attainment": sum(t <= slo_ttft_s for t in ttfts)
                / max(1, len(ttfts))},
        "pool": {"block_size": pool.block_size,
                 "num_blocks": pool.num_blocks,
                 "blocks_in_use_peak": pool.peak_blocks_in_use,
                 "dense_equiv_blocks": server.dense_equiv_blocks,
                 "reuse_hits": pool.reuse_hits - r0[0],
                 "reused_tokens": pool.reused_tokens - r0[1],
                 "evictions": pool.evictions - r0[2]},
        "per_request": [{"rid": r.rid, "prompt_len": int(len(r.prompt)),
                         "new_tokens": len(r.output),
                         "ttft_s": r.ttft_s(),
                         "per_token_s": r.per_token_s()}
                        for r in sorted(ok, key=lambda r: r.rid)],
        "outputs": {r.rid: list(r.output) for r in done},
    }


def main(full: bool = False, smoke: bool = False, arch: str = "minicpm_2b",
         tp: int = 1, num_requests: int = 0, arrival_rate: float = -1.0,
         slo_ttft: float = 1.0) -> None:
    import jax
    import numpy as np

    from repro.configs.base import ParallelConfig, get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.runtime.server import ServeConfig

    print("name,us_per_call,derived")
    cfg = get_smoke_config(arch)
    # (n_requests, max_prompt, max_new, max_batch, max_seq, block, chunk,
    #  rate): smoke keeps block/chunk small so the 3..12-token prompts still
    # span multiple blocks — reuse and chunking are exercised, cheaply
    if smoke:
        n_req, max_prompt, max_new, max_batch, max_seq = 4, 12, 4, 2, 64
        block, chunk, rate = 8, 8, 20.0
    elif full:
        n_req, max_prompt, max_new, max_batch, max_seq = 32, 96, 32, 8, 256
        block, chunk, rate = 16, 32, 5.0
    else:
        n_req, max_prompt, max_new, max_batch, max_seq = 8, 24, 8, 4, 128
        block, chunk, rate = 16, 16, 10.0
    if num_requests > 0:
        n_req = num_requests
    if arrival_rate >= 0:
        rate = arrival_rate
    if tp > len(jax.devices()):
        raise SystemExit(f"--tp {tp} > {len(jax.devices())} visible devices "
                         "(set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for a CPU sweep)")
    mesh = make_mesh(1, 1, tp)
    params = M.init_model(jax.random.PRNGKey(0), cfg,
                          ParallelConfig(tp=tp, dp=1))
    sc = ServeConfig(max_batch=max_batch, max_seq=max_seq, eos_token=-1,
                     max_new_tokens=max_new, block_size=block,
                     prefill_chunk=chunk)

    def reqs_factory():
        return _requests(cfg, n_req, max_prompt, np.random.default_rng(0))

    # one arrival schedule shared by every mode (fair comparison)
    offsets = _poisson_arrivals(n_req, rate, np.random.default_rng(1))

    doc = {"smoke": smoke, "full": full, "arch": arch, "tp": tp,
           "max_batch": max_batch, "max_seq": max_seq,
           "max_new_tokens": max_new, "requests": n_req,
           "arrival_rate_rps": rate, "slo_ttft_s": slo_ttft,
           "block_size": block, "prefill_chunk": chunk, "modes": []}
    ref_outputs = None
    # the wire lane rides decomposed with the int8 forward-wire transport:
    # serving has no backward, so the wire IS the whole quantization story
    # there.  Its outputs are allowed to drift (lossy wire); the fp-wire
    # mode lanes must still match each other exactly.
    lanes = [(mode, None) for mode in MODES] + [("decomposed", "int8")]
    for mode, wire in lanes:
        row = bench_mode(mode, cfg, params, mesh, sc, reqs_factory, offsets,
                         tp, slo_ttft, wire_dtype=wire)
        outputs = row.pop("outputs")
        # fp-wire overlap modes are numerics-preserving: serving outputs
        # must not depend on the seam transport
        if wire is None:
            row["outputs_match_reference"] = (ref_outputs is None
                                              or outputs == ref_outputs)
            ref_outputs = ref_outputs or outputs
        else:
            row["outputs_match_fp_wire"] = outputs == ref_outputs
        doc["modes"].append(row)
        tag = f"{mode}_wire-{wire}" if wire else mode
        us_per_tok = 1e6 * row["wall_s"] / max(row["new_tokens"], 1)
        print(f"serving_{tag}_tp{tp}_b{max_batch},{us_per_tok:.0f},"
              f"{row['tokens_per_s']:.1f}")
        print(f"serving_{tag}_ttft_p99,{1e6 * row['ttft_s']['p99']:.0f},"
              f"{row['slo']['attainment']:.2f}")

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(doc, f, indent=1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full problem sizes (use on real hardware)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI workload (verify.sh well-formedness gate)")
    ap.add_argument("--arch", default="minicpm_2b")
    ap.add_argument("--tp", type=int, default=1,
                    help="TP degree; at tp=1 the overlap modes are "
                         "transport-equivalent (single-shard fallback), so "
                         "the mode rows only gate numerics — seam timing "
                         "needs tp > 1 (real TPU, or forced host devices)")
    ap.add_argument("--num-requests", type=int, default=0,
                    help="override the preset request count")
    ap.add_argument("--arrival-rate", type=float, default=-1.0,
                    help="open-loop Poisson arrival rate, requests/s "
                         "(0 = all at t0; default: preset)")
    ap.add_argument("--slo-ttft", type=float, default=1.0,
                    help="TTFT SLO in seconds for the attainment metric")
    args = ap.parse_args()
    from repro.launch.cache import enable_compilation_cache
    enable_compilation_cache()
    main(**vars(args))
