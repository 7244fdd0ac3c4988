"""Serve ``minicpm_2b`` at its published widths on a TPU, end to end.

  python chip_smoke.py              # one chip: the serving path
  python chip_smoke.py --chips 4    # four chips, one process: tp=4 serving
                                    # (decomposed vs xla seams) and the tp=4
                                    # sequence-parallel trainer (flux vs xla)

The default run builds the published config (40 layers, d_model 2304, 36
heads, d_ff 5760, vocab 122753) with random weights from ``--seed``, serves
8 seeded requests through ``runtime.server.Server.serve`` — the path of
``python -m repro.launch.serve --arch minicpm_2b`` — and serves the same
prompts again on the same server.  It checks that every request returns its
32 tokens inside the vocabulary with no error, that the second pass is
token-identical to the first and that it reused cached prompt blocks.

With ``--chips 4`` it runs only that path.  Serving: both tp=4 servers take
the same requests; the decomposed server is then teacher-forced along the
xla server's tokens, and the logits both servers' decode and chunk programs
give at every position are compared.  Training: 3 trainer steps per
transport from the same parameters and data, compared by loss, by every
gradient (Adam's first moment) and by every parameter update; every step
must succeed (``Trainer.failures == 0``).

It exits non-zero, printing no ``ok`` line, when JAX finds no TPU, when
Pallas kernels would run in interpret mode (``REPRO_PALLAS_INTERPRET``), or
when any check fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
It starts no other process.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "minicpm_2b"
# The compared transports run the same bf16 model and differ only in the
# order the seams sum their bf16 partials, so they agree to bf16 rounding,
# not bit for bit.  Each limit sits between what sound transports give and
# what a planted fault gives (readings in PERF.md):
#: losses: one bf16 ulp, relative
LOSS_RTOL = 2.0 ** -8
#: serving: worst position's ||dlogits|| / ||logits||, vocab-centred
LOGIT_RTOL = 0.05
#: serving: how far the emitted token's kept logit may trail the kept
#: maximum, relative to max |logit|.  The kept logits are the vocab GEMM's
#: bf16 output; on the TPU the program's argmax can read a fused copy that
#: was never rounded to bf16, so the two may part by a bf16 ulp (2**-7)
ARGMAX_RTOL = 2.0 ** -6
#: training: worst leaf's ||d mu|| / ||mu|| (Adam's first moment: the
#: gradients of all steps) and ||d(p - p0)|| / ||p - p0|| (the updates)
GRAD_RTOL = 0.1
UPDATE_RTOL = 0.3
#: --chips 4 trainer batch: 8 x 256 tokens, 512 rows per shard.  Params,
#: grads and fp32 moments take ~8.2 GB per chip at tp=4; the step's
#: compile-time footprint is 6.6 GiB args + 5.8 GiB temp, inside 16 GB.
TRAIN_BATCH, TRAIN_SEQ = 8, 256


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def _requests(cfg, seed: int, n: int = 8):
    import numpy as np

    from repro.runtime.server import Request
    rng = np.random.default_rng(seed)
    lens = np.linspace(64, 768, n).astype(int)
    prompts = [rng.integers(0, cfg.vocab_size, size=(int(L),)).astype(np.int32)
               for L in lens]
    return lambda: [Request(rid=i, prompt=p) for i, p in enumerate(prompts)]


def _serve_pass(server, make_requests, label: str):
    chunks, steps = server.prefill_dispatches, server.decode_dispatches
    t0 = time.perf_counter()
    done = server.serve(make_requests())
    wall = time.perf_counter() - t0
    done = sorted(done, key=lambda r: r.rid)
    n_tok = sum(len(r.output) for r in done)
    log(f"[{label}] {len(done)} requests, {n_tok} tokens generated in "
        f"{wall:.3f}s wall ({n_tok / wall:.1f} tok/s incl. host); "
        f"{server.prefill_dispatches - chunks} chunk + "
        f"{server.decode_dispatches - steps} decode dispatches")
    return done


def _check_outputs(done, n_req: int, vocab: int, max_new: int, label: str):
    check(len(done) == n_req, f"{label}: {len(done)}/{n_req} requests done")
    for r in done:
        check(r.error is None, f"{label}: rid {r.rid} rejected: {r.error}")
        check(len(r.output) == max_new,
              f"{label}: rid {r.rid} gave {len(r.output)} tokens, "
              f"want {max_new}")
        check(all(0 <= t < vocab for t in r.output),
              f"{label}: rid {r.rid} emitted a token outside [0, {vocab})")
    return [list(r.output) for r in done]


def _server(cfg, mesh, mode: str, params, keep_logits: bool = False):
    from repro.configs.base import ParallelConfig
    from repro.runtime.server import ServeConfig, Server
    tp = mesh.shape["model"]
    par = ParallelConfig(tp=tp, dp=1, overlap_mode=mode)
    sc = ServeConfig(max_batch=8, max_seq=1024, block_size=16,
                     prefill_chunk=256, eos_token=-1, max_new_tokens=32,
                     keep_logits=keep_logits)
    server = Server(cfg, par, mesh, params, sc)
    secs = server.compile()
    log(f"[serve tp={tp} {mode}] compile: decode {secs['decode']:.2f}s, "
        f"chunk {secs['chunk']:.2f}s")
    return server


def _init_params(cfg, mesh, seed: int):
    """Random weights from ``seed``, built sharded as the servers hold
    them (no device ever holds the whole tree)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import ParallelConfig
    from repro.models import model as M
    t0 = time.perf_counter()
    par = ParallelConfig(tp=mesh.shape["model"], dp=1)
    init = functools.partial(M.init_model, cfg=cfg, par=par)
    key = jax.random.PRNGKey(seed)
    specs = M.param_specs(cfg, par, jax.eval_shape(init, key))
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.block_until_ready(
        jax.jit(init, out_shardings=shardings)(key))
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"init_model: {n} parameters in {time.perf_counter() - t0:.2f}s "
        f"(compile included)")
    return params


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def one_chip(cfg, seed: int) -> None:
    import jax

    from repro.launch.mesh import make_mesh
    mesh = make_mesh(1, 1, 1)
    server = _server(cfg, mesh, "decomposed", _init_params(cfg, mesh, seed))
    make = _requests(cfg, seed)
    n_req, vocab, max_new = 8, cfg.vocab_size, server.sc.max_new_tokens
    first = _check_outputs(_serve_pass(server, make, "pass 1"), n_req, vocab,
                           max_new, "pass 1")
    hits = server.pool.reuse_hits
    second = _check_outputs(_serve_pass(server, make, "pass 2"), n_req,
                            vocab, max_new, "pass 2")
    reuse = server.pool.reuse_hits - hits
    log(f"[pass 2] reuse_hits={reuse} "
        f"reused_tokens={server.pool.reused_tokens}")
    check(second == first, "pass 2 tokens differ from pass 1")
    check(reuse > 0, "pass 2 reused no cached prompt block")
    log(f"peak_bytes_in_use: {_peak_bytes(jax.devices()[0])}")


def logit_errors(ref, other):
    """Per position (every request, every emitted token): ||dl|| / ||l|| of
    the vocab-centred logits of ``other`` against ``ref``."""
    import numpy as np
    out = []
    for ra, rb in zip(ref, other):
        for la, lb in zip(ra, rb):
            la = la.astype(np.float64) - la.mean()
            lb = lb.astype(np.float64) - lb.mean()
            out.append(float(np.linalg.norm(lb - la) / np.linalg.norm(la)))
    return np.array(out)


def _serve_tp4(cfg, mesh, seed: int, expect) -> None:
    """Decode all-reduce and chunk-prefill seams, ring (decomposed) against
    the monolithic collective (xla).  Free-running greedy streams of a
    random-weight model part at the first near-tie, because the transports
    round differently; so the decomposed server is also teacher-forced
    along the xla tokens and the two servers' logits are compared at every
    position."""
    import numpy as np
    params = _init_params(cfg, mesh, seed)
    make = _requests(cfg, seed)
    n_req, vocab = 8, cfg.vocab_size
    tokens, logits = {}, {}
    for mode in ("xla", "decomposed"):
        server = _server(cfg, mesh, mode, params, keep_logits=True)
        done = _serve_pass(server, make, f"serve tp=4 {mode}")
        tokens[mode] = _check_outputs(done, n_req, vocab,
                                      server.sc.max_new_tokens,
                                      f"serve tp=4 {mode}")
        if mode == "xla":
            logits[mode] = [r.logits for r in done]
            gaps = np.array([(lg.max() - lg[t]) / np.abs(lg).max()
                             for r in done
                             for lg, t in zip(r.logits, r.output)])
            log(f"[serve tp=4 xla] emitted tokens that are not their kept "
                f"logits' argmax: {int(np.sum(gaps > 0))}/{gaps.size}, "
                f"worst gap {gaps.max()} of max |logit| "
                f"(limit {ARGMAX_RTOL})")
            expect(gaps.max() <= ARGMAX_RTOL,
                   "xla server: an emitted token is not its logits' argmax")
            continue
        forced = make()
        for r, t in zip(forced, tokens["xla"]):
            r.forced = t
        done = _serve_pass(server, lambda: forced, f"serve tp=4 {mode} "
                           f"teacher-forced")
        preds = _check_outputs(done, n_req, vocab, server.sc.max_new_tokens,
                               f"serve tp=4 {mode} teacher-forced")
        logits[mode] = [r.logits for r in done]
        del server
    del params
    agree = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b), len(x))
             for x, y in zip(tokens["decomposed"], tokens["xla"])]
    log(f"[serve tp=4] free-running tokens identical before the first "
        f"difference, per request (of 32): {agree}")
    same = sum(a == b for x, y in zip(preds, tokens["xla"])
               for a, b in zip(x, y))
    log(f"[serve tp=4] teacher-forced decomposed predictions equal to the "
        f"xla tokens: {same}/{n_req * 32}")
    err = logit_errors(logits["xla"], logits["decomposed"])
    log(f"[serve tp=4] logits |decomposed - xla| / |xla| over {err.size} "
        f"positions: worst {err.max()}, median {float(np.median(err))} "
        f"(limit {LOGIT_RTOL})")
    expect(err.size == n_req * 32 and err.max() <= LOGIT_RTOL,
           "tp=4 serving: decomposed and xla logits disagree")


def _leaf_errors(got, ref, base=None):
    """Per leaf ||got - ref|| / ||ref - base|| (base 0 when None), in fp32
    on the devices; ``ref``/``base`` may be host arrays."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(g, r, b):
        f32 = lambda x: x.astype(jnp.float32)
        return (jnp.linalg.norm(f32(g) - f32(r)),
                jnp.linalg.norm(f32(r) - f32(b)))

    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    refs = jax.tree.leaves(ref)
    bases = jax.tree.leaves(base) if base is not None else [None] * len(refs)
    for (path, g), r, b in zip(flat, refs, bases):
        r = jax.device_put(r, g.sharding)
        b = jnp.zeros_like(g) if b is None else jax.device_put(b, g.sharding)
        num, den = (float(x) for x in norms(g, r, b))
        out[jax.tree_util.keystr(path)] = (num, den)
    return out


def _train_tp4(cfg, mesh, seed: int, expect, batch: int = TRAIN_BATCH,
               seq: int = TRAIN_SEQ) -> None:
    """The fused ag_gemm/gemm_rs kernels (flux) against the XLA collectives
    in the sequence-parallel train step, forward and backward: 3 steps from
    the same parameters and data at the full learning rate (no warmup), so
    every step updates the parameters."""
    import jax
    import numpy as np

    from repro.configs.base import ParallelConfig
    from repro.data.pipeline import DataConfig
    from repro.runtime.trainer import TrainConfig, Trainer
    losses, state = {}, {}
    for mode in ("xla", "flux"):
        tr = Trainer(cfg, ParallelConfig(tp=4, dp=1, overlap_mode=mode), mesh,
                     TrainConfig(total_steps=3, warmup_steps=0, seed=seed))
        tr.data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                 global_batch=batch, seed=seed)
        params, opt = tr.init_state()
        if mode == "xla":
            p0 = jax.device_get(params)
        t0 = time.perf_counter()
        params, opt, hist = tr.train(params, opt)
        losses[mode] = [h["loss"] for h in hist]
        log(f"[train tp=4 {mode}] batch {batch} x seq {seq}: losses "
            f"{losses[mode]} in {time.perf_counter() - t0:.2f}s "
            f"(compile included), failures={tr.failures}")
        expect(tr.failures == 0, f"train {mode}: {tr.failures} failed steps")
        expect(len(hist) == 3 and bool(np.all(np.isfinite(losses[mode]))),
               f"train {mode}: losses {losses[mode]}")
        if mode == "xla":
            state = {"params": jax.device_get(params),
                     "mu": jax.device_get(opt["mu"])}
            del params, opt
    if len(losses["flux"]) == len(losses["xla"]):
        diff = np.abs(np.array(losses["flux"]) - np.array(losses["xla"]))
        log(f"[train tp=4] |flux - xla| losses = {diff.tolist()} "
            f"(rtol {LOSS_RTOL})")
        expect(bool(np.all(diff <= LOSS_RTOL * np.abs(losses["xla"]))),
               "flux and xla losses disagree")
    for name, got, ref, base, limit in (
            ("gradients (Adam mu)", opt["mu"], state["mu"], None, GRAD_RTOL),
            ("updates (p - p0)", params, state["params"], p0, UPDATE_RTOL)):
        errs = _leaf_errors(got, ref, base)
        rel = {k: n / d for k, (n, d) in errs.items() if d > 0}
        frozen = sorted(k for k, (n, d) in errs.items() if d == 0)
        worst = max(rel, key=rel.get)
        log(f"[train tp=4] {name}: |flux - xla| / |xla| worst leaf "
            f"{worst} {rel[worst]}, median {float(np.median(list(rel.values())))}"
            f" over {len(rel)} leaves (limit {limit})")
        if frozen:
            log(f"[train tp=4] {name}: unchanged under xla: {frozen}, "
                f"|flux - xla| {[errs[k][0] for k in frozen]}")
        expect(rel[worst] <= limit and all(errs[k][0] == 0 for k in frozen),
               f"flux and xla {name} disagree")


def four_chips(cfg, seed: int, batch: int = TRAIN_BATCH,
               seq: int = TRAIN_SEQ) -> None:
    """Both comparisons run to the end before any check fails, so one run
    reports all of them."""
    import jax

    from repro.launch.mesh import make_mesh
    failed = []

    def expect(cond: bool, msg: str) -> None:
        if not cond:
            log(f"CHECK FAILED: {msg}")
            failed.append(msg)

    mesh = make_mesh(1, 1, 4)
    _serve_tp4(cfg, mesh, seed, expect)
    _train_tp4(cfg, mesh, seed, expect, batch, seq)
    for i, dev in enumerate(jax.devices()):
        log(f"device {i} peak_bytes_in_use: {_peak_bytes(dev)}")
    check(not failed, "; ".join(failed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import compat
        from repro.configs.base import get_config
        from repro.launch.cache import enable_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"{compat.version_summary()}; device_kind={dev.device_kind!r} "
        f"platform={dev.platform} count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    if compat.interpret_default():
        print("chip_smoke: Pallas kernels would run in interpret mode on the "
              "chip (REPRO_PALLAS_INTERPRET is set)", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    log(f"compilation cache: {enable_compilation_cache()}")

    cfg = get_config(ARCH)
    log(f"{ARCH}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    try:
        if args.chips == 1:
            one_chip(cfg, args.seed)
        else:
            four_chips(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
