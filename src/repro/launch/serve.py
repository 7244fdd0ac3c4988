"""Serving launcher: continuous batching over a model checkpoint (or random
init for smoke runs).

  PYTHONPATH=src python -m repro.launch.serve --arch phi4_mini_38b --smoke \
      --requests 8
"""
import argparse

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ParallelConfig, get_config, get_smoke_config
from repro.launch.cache import enable_compilation_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.runtime.server import Request, ServeConfig, Server


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mode", default="decomposed")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["int8", "fp8_e4m3", "int4"],
                    help="forward-wire precision for the TP seams (lossy; "
                         "serving has no backward, so this is the full "
                         "quantization story here)")
    ap.add_argument("--max-logit-rmse", type=float, default=None,
                    help="error budget for the --autotune wire_dtype sweep")
    ap.add_argument("--plan-profile", default=None,
                    help="tuned per-seam profile JSON (repro.tuning)")
    ap.add_argument("--autotune", action="store_true",
                    help="tune seam plans first (decode_ar at --max-batch, "
                         "matching the server's decode jit signature); "
                         "requires --tp > 1 — there are no seams to tune "
                         "on a single shard")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="concurrent decode slots (the server's jit batch)")
    ap.add_argument("--eos", type=int, default=-1,
                    help="EOS token id (-1: never stop early)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV pool block (page) size in tokens")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-prefill rows per dispatch (bounds how "
                         "long a long prompt stalls running decodes)")
    args = ap.parse_args()
    enable_compilation_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    par = ParallelConfig(tp=args.tp, dp=args.dp, overlap_mode=args.mode,
                         wire_dtype=args.wire_dtype,
                         max_logit_rmse=args.max_logit_rmse,
                         plan_profile=args.plan_profile)
    if args.autotune and args.tp <= 1:
        print("warning: --autotune skipped (tp=1 has no TP seams to tune); "
              "pass --tp > 1 to tune the serving plans")
    if args.autotune and args.tp > 1:
        import dataclasses
        import os

        from repro.tuning import (WIRE_DTYPE_SWEEP, PlanRegistry,
                                  autotune_model, default_plans_dir)
        path = args.plan_profile or os.path.join(
            default_plans_dir(), f"{args.arch}_tp{args.tp}.json")
        reg = PlanRegistry.open(path, n_dev=args.tp)
        wire_sweep = None
        if args.wire_dtype:
            wire_sweep = (None, args.wire_dtype)
        elif args.max_logit_rmse is not None:
            wire_sweep = WIRE_DTYPE_SWEEP
        autotune_model(cfg, par, decode_batch=args.max_batch,
                       registry=reg, save_path=path,
                       wire_dtypes=wire_sweep,
                       max_logit_rmse=args.max_logit_rmse)
        par = dataclasses.replace(par, plan_profile=path)
    mesh = make_mesh(1, args.dp, args.tp)
    params = M.init_model(jax.random.PRNGKey(0), cfg, par)

    sc = ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                     eos_token=args.eos, max_new_tokens=args.max_new,
                     block_size=args.block_size,
                     prefill_chunk=args.prefill_chunk)
    server = Server(cfg, par, mesh, params, sc)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=(8 + i,)).astype(np.int32))
        for i in range(args.requests)]
    done = server.serve(reqs)
    for r in sorted(done, key=lambda x: x.rid):
        ttft = r.ttft_s()
        ttft_ms = f"{ttft * 1e3:.1f}ms" if ttft is not None else "n/a"
        print(f"req {r.rid}: +{len(r.output)} tokens ttft={ttft_ms}: "
              f"{r.output[:12]}")
    pool = server.pool
    print(f"pool: peak {pool.peak_blocks_in_use}/{pool.num_blocks - 1} "
          f"blocks (dense equiv {server.dense_equiv_blocks}), "
          f"reuse_hits={pool.reuse_hits} reused_tokens={pool.reused_tokens} "
          f"evictions={pool.evictions}")


if __name__ == "__main__":
    main()
