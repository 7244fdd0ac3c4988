"""Error budget for low-precision wire transports (ISSUE: wire_dtype).

Quantizing the forward wire (``FusedOp.wire_dtype``) trades accuracy for
bytes-on-wire.  The autotuner must therefore never pick a wire on time
alone — every quantized candidate is scored against an ERROR BUDGET
(``max_logit_rmse``) before it is allowed to win.  This module supplies
the deviation estimates at three costs:

  codec_rmse       pure codec roundtrip deviation — one encode/decode of
                   a seeded activation tensor.  Deviceless, instant.
  seam_wire_rmse   per-seam deviation proxy — SIMULATES what the seam's
                   transport does to the payload (one roundtrip for
                   ag/a2a; hop-by-hop accumulator requantization for the
                   rs/ar rings, which compounds).  Deviceless; this is
                   the default ``rmse_fn`` of ``autotune.tune_seam``.
  model_logit_rmse end-to-end logit deviation of a real model forward,
                   fp wire vs quantized wire, identical params/tokens.
                   Needs >= tp devices (interpret/host-count fine); used
                   by the oracle tests and the tuning benchmark.

All three return a RELATIVE rmse (deviation RMS / signal RMS) so one
``max_logit_rmse`` threshold is meaningful across seams and shapes.  The
backward path never enters the budget: cotangents always ride the
full-precision transports (see core.overlap), so wire_dtype perturbs the
forward value only.
"""
from __future__ import annotations

import functools
from typing import Optional

__all__ = ["codec_rmse", "seam_wire_rmse", "model_logit_rmse",
           "DEFAULT_MAX_LOGIT_RMSE"]

# A permissive default for CLI flows that ask for a wire sweep without
# naming a budget: rejects int4 on deep rings, admits int8/fp8 broadly.
DEFAULT_MAX_LOGIT_RMSE = 0.05

_PROXY_D = 512          # divisible by the 128-block and by n_dev <= 8
_PROXY_ROWS = 32


def _rel_rmse(ref, got):
    import jax.numpy as jnp
    num = jnp.sqrt(jnp.mean((ref - got) ** 2))
    den = jnp.maximum(jnp.sqrt(jnp.mean(ref ** 2)), 1e-30)
    return float(num / den)


def _roundtrip(x, wire_dtype):
    from repro.core.overlap import wire_decode, wire_encode
    return wire_decode(wire_encode(x, wire_dtype), wire_dtype, x.dtype)


def codec_rmse(wire_dtype: Optional[str], *, d: int = _PROXY_D,
               rows: int = _PROXY_ROWS, seed: int = 0) -> float:
    """Relative rmse of one encode/decode roundtrip on seeded N(0,1)
    activations.  The fp wire is exact by definition."""
    if wire_dtype is None:
        return 0.0
    import jax
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d), "float32")
    return _rel_rmse(x, _roundtrip(x, wire_dtype))


@functools.lru_cache(maxsize=256)
def _seam_wire_rmse_cached(kind: str, n_dev: int, wire_dtype: str,
                           seed: int) -> float:
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), n_dev)
    parts = [jax.random.normal(k, (_PROXY_ROWS, _PROXY_D), "float32")
             for k in keys]
    if kind in ("ag", "a2a"):
        # one roundtrip per travelling shard; errors are independent so
        # the gathered deviation equals the per-shard deviation
        exact = jnp.concatenate(parts, axis=0)
        got = jnp.concatenate([_roundtrip(p, wire_dtype) for p in parts],
                              axis=0)
        return _rel_rmse(exact, got)
    # rs / ar: the ring requantizes the travelling ACCUMULATOR every hop,
    # so the deviation compounds over the n_dev-1 reduce hops
    exact = sum(parts[1:], parts[0])
    acc = parts[0]
    for p in parts[1:]:
        acc = _roundtrip(acc, wire_dtype) + p
    if kind == "ar":
        # the all-gather phase ships the reduced shard through the wire
        # once more before it lands on every non-owner device
        acc = _roundtrip(acc, wire_dtype)
    return _rel_rmse(exact, acc)


def seam_wire_rmse(kind: str, m: int, n: int, k: int, n_dev: int,
                   wire_dtype: Optional[str], *, seed: int = 0) -> float:
    """Deviation proxy for one seam's wire — the default ``rmse_fn`` of
    ``autotune.tune_seam``.  The proxy is shape-independent (relative
    rmse of the codec is scale- and width-invariant for seeded gaussian
    payloads) but RING-DEPTH dependent: rs/ar compound over n_dev-1 hop
    requantizations, ag/a2a pay a single roundtrip."""
    del m, n, k  # relative rmse is shape-invariant; depth is what matters
    if wire_dtype is None:
        return 0.0
    return _seam_wire_rmse_cached(kind, max(int(n_dev), 2), wire_dtype,
                                  seed)


def model_logit_rmse(cfg, par, wire_dtype: Optional[str], *,
                     mode: str = "decomposed", comm_chunks: int = 0,
                     batch: int = 2, seq: int = 64, seed: int = 0,
                     plans=None) -> float:
    """End-to-end logit deviation: ONE model, ONE token batch, forward
    under the fp wire and under ``wire_dtype``, relative rmse over the
    valid vocab slice.  ``plans`` overrides the fp-wire PlanSet (default:
    ``PlanSet.uniform(mode, comm_chunks)``); the quantized run uses the
    same set stamped via ``with_wire_dtype``.  Requires >= par.tp
    devices; interpret mode is fine (the quantized rings are pure lax)."""
    import functools as _ft

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.compat import shard_map
    from repro.models import layers
    from repro.models import model as M
    from repro.parallel.sharding import TPContext, pad_vocab
    from repro.tuning.plans import PlanSet

    tp = par.tp
    mesh = Mesh(np.array(jax.devices()[:tp]).reshape(1, tp),
                ("data", "model"))
    params = M.init_model(jax.random.PRNGKey(seed), cfg, par)
    specs = M.param_specs(cfg, par, params)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq), 0, cfg.vocab_size)
    v_pad = pad_vocab(cfg.vocab_size, tp)

    if plans is None:
        plans = PlanSet.uniform(mode, comm_chunks)

    def run(plan_set):
        ctx = TPContext(axis="model", dp_axes=("data",),
                        ep_axes=("model",) if cfg.moe else (),
                        mode=mode, comm_chunks=comm_chunks,
                        plans=plan_set)

        @jax.jit
        @_ft.partial(shard_map, mesh=mesh,
                     in_specs=(specs, P(None, None)),
                     out_specs=P(None, None, "model"), check_vma=False)
        def logits_fn(p, t):
            x = layers.embed_lookup(p["embed"], t, ctx, v_pad)
            x = x.astype(cfg.compute_dtype)
            h, _ = M.backbone(p, x, ctx, cfg, par)
            h = layers.rms_norm(h, p["final_norm"], cfg.norm_eps)
            return layers.lm_head_logits(h, layers.head_table(p), ctx)

        out = logits_fn(params, tokens)
        return jnp.asarray(out, jnp.float32)[..., :cfg.vocab_size]

    ref = run(plans)
    if wire_dtype is None:
        return 0.0
    got = run(plans.with_wire_dtype(wire_dtype))
    return _rel_rmse(ref, got)
