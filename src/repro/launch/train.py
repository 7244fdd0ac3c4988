"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch codeqwen15_7b \
      --smoke --steps 20          # reduced config, CPU
  python -m repro.launch.train --arch qwen15_110b --tp 16 --dp 16 \
      --steps 1000 --mode flux    # production mesh (TPU pod)
"""
import argparse
import dataclasses
import logging

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ParallelConfig, get_config, get_smoke_config
from repro.launch.cache import enable_compilation_cache
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamWConfig
from repro.runtime import trainer as T


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--ep", type=int, default=0,
                    help="dedicated expert-parallel mesh axis size (0 = no "
                         "'ep' axis; EP implied over 'model' or, for big "
                         "expert counts, ('data','model'))")
    from repro.core.overlap import VALID_MODES
    ap.add_argument("--mode", default="decomposed", choices=list(VALID_MODES))
    ap.add_argument("--comm-chunks", type=int, default=0,
                    help="ring sub-chunking (0 = auto)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["int8", "fp8_e4m3", "int4"],
                    help="forward-wire precision for the TP seams (lossy "
                         "on the forward value only; cotangents always "
                         "ride the full-precision transports)")
    ap.add_argument("--max-logit-rmse", type=float, default=None,
                    help="error budget for the --autotune wire_dtype "
                         "sweep: a quantized wire may only win a seam "
                         "when its estimated logit deviation fits")
    ap.add_argument("--plan-profile", default=None,
                    help="tuned per-seam profile JSON (repro.tuning)")
    ap.add_argument("--scatter-axis", default="auto",
                    choices=["auto", "seq", "hidden"],
                    help="residual-stream activation layout between TP "
                         "seams: seq = sequence-sharded (Megatron-SP, "
                         "~1/tp activation residency), hidden = "
                         "replicated; auto = tuned profile / default")
    ap.add_argument("--autotune", action="store_true",
                    help="tune every seam before training and save the "
                         "profile to experiments/plans/ (measured on real "
                         "devices, roofline fallback otherwise)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default=None,
                    help="cosine|wsd (default: per-arch)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--zero3", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    args = ap.parse_args()
    enable_compilation_cache()

    logging.basicConfig(level=logging.INFO)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    par = ParallelConfig(tp=args.tp, dp=args.dp, pods=args.pods,
                         ep=args.ep,
                         overlap_mode=args.mode, zero3=args.zero3,
                         wire_dtype=args.wire_dtype,
                         max_logit_rmse=args.max_logit_rmse,
                         comm_chunks=args.comm_chunks,
                         plan_profile=args.plan_profile,
                         scatter_axis=args.scatter_axis,
                         grad_compress=args.grad_compress,
                         ep_over_dp=(args.ep <= 1
                                     and cfg.moe is not None
                                     and cfg.moe.num_experts > 16),
                         fuse_w13=True)
    if args.autotune and args.tp > 1:
        import os
        from repro.tuning import (WIRE_DTYPE_SWEEP, PlanRegistry,
                                  autotune_model, default_plans_dir)
        path = args.plan_profile or os.path.join(
            default_plans_dir(), f"{args.arch}_tp{args.tp}.json")
        reg = PlanRegistry.open(path, n_dev=args.tp)
        # a budget opts the sweep into quantized wires; a pinned
        # --wire-dtype restricts it to (fp, that wire)
        wire_sweep = None
        if args.wire_dtype:
            wire_sweep = (None, args.wire_dtype)
        elif args.max_logit_rmse is not None:
            wire_sweep = WIRE_DTYPE_SWEEP
        autotune_model(cfg, par, tokens_per_dp=args.batch * args.seq // args.dp,
                       registry=reg, save_path=path,
                       wire_dtypes=wire_sweep,
                       max_logit_rmse=args.max_logit_rmse)
        par = dataclasses.replace(par, plan_profile=path)
        logging.info("autotuned seam plans -> %s", path)
    mesh = make_mesh(args.pods, args.dp, args.tp, ep=max(args.ep, 1))

    schedule = args.schedule or (
        "wsd" if args.arch.startswith("minicpm") else "cosine")
    tc = T.TrainConfig(total_steps=args.steps, warmup_steps=args.steps // 10,
                       base_lr=args.lr, schedule=schedule,
                       checkpoint_dir=args.ckpt_dir, log_every=10)
    tr = T.Trainer(cfg, par, mesh, tc, AdamWConfig(lr=args.lr))
    tr.data_cfg = dataclasses.replace(
        tr.data_cfg, seq_len=args.seq, global_batch=args.batch)
    params, opt, hist = tr.train(resume=args.ckpt_dir is not None)
    print(f"final loss: {hist[-1]['loss']:.4f} "
          f"(start {hist[0]['loss']:.4f}); straggler events "
          f"{tr.straggler_events}; failures {tr.failures}")


if __name__ == "__main__":
    main()
