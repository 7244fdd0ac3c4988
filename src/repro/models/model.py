"""Model assembly: every assigned architecture builds from the same blocks.

A model is ``num_layers / len(pattern)`` repetitions ("periods") of its layer
pattern.  Homogeneous periods are scanned (keeps HLO small at 61+ layers);
positions inside a period are python-unrolled (heterogeneous: Jamba's
mamba/attn interleave, DeepSeek's dense-lead + MoE).

Parameters are GLOBAL arrays; ``param_specs`` returns the matching
PartitionSpec tree; all forward code runs inside ``compat.shard_map``
(the JAX-version-portable wrapper in ``repro/compat``) and sees local
shards.  ``zero3`` additionally shards big weights over the data axis and
gathers them per-layer (the paper §2.1's "easily prefetched" AllGather
pattern — ZeRO-3/FSDP).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, DENSE_FFN, MLA, MAMBA, MOE_FFN, RWKV,
                                ModelConfig, ParallelConfig, ShapeConfig)
from repro.core import overlap
from repro.models import attention, ffn, layers, mamba, rwkv
from repro.parallel.sharding import (TPContext, pad_ff, pad_heads,
                                     pad_kv_heads, pad_vocab)

Array = jax.Array


# ---------------------------------------------------------------------------
# Pattern expansion
# ---------------------------------------------------------------------------
def expanded_pattern(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """Full per-layer (mixer, ffn) list, honoring leading dense layers."""
    period = len(cfg.pattern)
    reps = cfg.num_layers // period
    assert reps * period == cfg.num_layers, (
        f"{cfg.name}: num_layers {cfg.num_layers} not a multiple of pattern "
        f"period {period}")
    out = [cfg.pattern[i % period] for i in range(cfg.num_layers)]
    for i in range(cfg.leading_dense_layers):
        out[i] = (out[i][0], DENSE_FFN)
    return out


def n_periods(cfg: ModelConfig) -> int:
    return (cfg.num_layers - cfg.leading_dense_layers) // len(cfg.pattern)


# ---------------------------------------------------------------------------
# Per-position init / specs / apply dispatch
# ---------------------------------------------------------------------------
def _init_mixer(key, kind: str, cfg: ModelConfig, tp: int, dtype,
                fuse13: bool = False):
    if kind == ATTN:
        return attention.init_gqa(key, cfg, tp, dtype)
    if kind == MLA:
        return attention.init_mla(key, cfg, tp, dtype)
    if kind == MAMBA:
        return mamba.init_mamba(key, cfg, tp, dtype, fuse_xz=fuse13)
    if kind == RWKV:
        return rwkv.init_rwkv_time(key, cfg, tp, dtype)
    raise ValueError(kind)


def _init_ffn(key, kind: str, cfg: ModelConfig, ep: int, tp: int, dtype,
              fuse13: bool = False):
    if kind == DENSE_FFN:
        return ffn.init_ffn(key, cfg.d_model, cfg.d_ff, tp, dtype,
                            fuse13=fuse13)
    if kind == MOE_FFN:
        return ffn.init_moe(key, cfg, ep, tp, dtype, fuse13=fuse13)
    if kind == RWKV:  # rwkv channel-mix plays the ffn role
        return rwkv.init_rwkv_channel(key, cfg, tp, dtype)
    raise ValueError(kind)


_MIXER_SPECS = {
    ATTN: {"wqkv": P(None, "model"), "wo": P("model", None), "norm": P(None),
           "bqkv": P("model")},
    MLA: {"w_q": P(None, "model"), "w_dq": P(None, None),
          "w_uq": P(None, "model"),
          "w_dkv": P(None, None), "w_ukv": P(None, "model"),
          "w_o": P("model", None), "q_norm": P(None), "kv_norm": P(None),
          "norm": P(None)},
    MAMBA: {"w_in_x": P(None, "model"), "w_in_z": P(None, "model"),
            "w_in_xz": P(None, "model"),
            "conv": P(None, "model"), "conv_b": P("model"),
            "w_x": P("model", None), "w_dt": P(None, "model"),
            "dt_bias": P("model"), "a_log": P("model", None),
            "d_skip": P("model"), "w_out": P("model", None), "norm": P(None)},
    RWKV: {"mu": P(None, None), "w_r": P(None, "model"),
           "w_k": P(None, "model"), "w_v": P(None, "model"),
           "w_g": P(None, "model"), "w_dec1": P(None, None),
           "w_dec2": P(None, "model"), "dec_base": P("model"),
           "u_bonus": P("model"), "w_o": P("model", None),
           "ln_x": P(None), "norm": P(None)},
}

_FFN_SPECS = {
    DENSE_FFN: {"w1": P(None, "model"), "w3": P(None, "model"),
                "w13": P(None, "model"), "w2": P("model", None),
                "norm": P(None)},
    RWKV: {"mu": P(None, None), "w_k": P(None, "model"),
           "w_v": P("model", None), "w_r": P(None, None), "norm": P(None)},
}


def _moe_specs(ep_axes: Tuple[str, ...]) -> Dict:
    e = P(ep_axes if len(ep_axes) > 1 else ep_axes[0]) if ep_axes else P(None)
    espec = ep_axes if not ep_axes else (
        tuple(ep_axes) if len(ep_axes) > 1 else ep_axes[0])
    return {
        "router": P(None, None),
        "w1": P(espec or None, None, None),
        "w3": P(espec or None, None, None),
        "w2": P(espec or None, None, None),
        "norm": P(None),
        "shared": {"w1": P(None, "model"), "w3": P(None, "model"),
                   "w13": P(None, "model"), "w2": P("model", None)},
    }


def _specs_for(params: Dict, table: Dict) -> Dict:
    """Prune the spec table to the keys that actually exist."""
    out = {}
    for k, v in params.items():
        if isinstance(v, dict):
            out[k] = _specs_for(v, table[k])
        else:
            out[k] = table[k]
    return out


def _zero3_leaf_flag(spec: P, shape: Tuple[int, ...], dp: int) -> bool:
    """True when a (non-stacked) leaf is ZeRO-3 dim0-sharded over 'data':
    a 2-D+ weight whose dim0 is free in the spec and divisible by dp."""
    if len(shape) < 2 or shape[0] % max(dp, 1) or shape[0] < dp:
        return False
    parts = list(spec) + [None] * (len(shape) - len(spec))
    return parts[0] is None


def zero3_flags(cfg: ModelConfig, par: ParallelConfig) -> Dict:
    """Static bool trees (per layer position) marking ZeRO-3 leaves — shared
    by param_specs (spec building) and the forward pass (per-layer gather).
    Evaluated on the UNSTACKED layer structure."""
    if not par.zero3:
        return {"lead": None, "periods": None}
    pat = expanded_pattern(cfg)

    def one(kind_pair):
        ex = jax.eval_shape(
            lambda: {"mixer": _init_mixer(jax.random.PRNGKey(0), kind_pair[0],
                                          cfg, par.tp, jnp.bfloat16,
                                          par.fuse_w13),
                     "ffn": _init_ffn(jax.random.PRNGKey(0), kind_pair[1],
                                      cfg, _ep_size(cfg, par), par.tp,
                                      jnp.bfloat16, par.fuse_w13)})
        spec = _layer_spec(kind_pair, cfg, par, ex)
        return jax.tree.map(
            lambda sp, pl: _zero3_leaf_flag(sp, pl.shape, par.dp),
            spec, ex, is_leaf=lambda x: isinstance(x, P))

    return {"lead": [one(pat[i]) for i in range(cfg.leading_dense_layers)],
            "periods": [one(kp) for kp in cfg.pattern]}


# ---------------------------------------------------------------------------
# Model init + specs
# ---------------------------------------------------------------------------
def init_model(key, cfg: ModelConfig, par: ParallelConfig,
               dtype=jnp.bfloat16) -> Dict:
    tp = par.tp
    ep = _ep_size(cfg, par)
    v_pad = pad_vocab(cfg.vocab_size, tp)
    keys = jax.random.split(key, cfg.num_layers + 4)

    from repro.models import init_utils as iu
    params: Dict[str, Any] = {
        "embed": iu.zero_pad_rows(
            jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model))
            * cfg.d_model ** -0.5, v_pad).astype(dtype),
        "final_norm": layers.init_rms_norm(cfg.d_model, dtype),
    }
    pat = expanded_pattern(cfg)
    lead = cfg.leading_dense_layers
    # leading (unstacked) layers
    if lead:
        params["lead"] = [
            {"mixer": _init_mixer(keys[1 + i], pat[i][0], cfg, tp, dtype,
                                  par.fuse_w13),
             "ffn": _init_ffn(keys[1 + i], pat[i][1], cfg, ep, tp, dtype,
                              par.fuse_w13)}
            for i in range(lead)]
    # scanned periods: stack per pattern position
    reps = n_periods(cfg)
    period = cfg.pattern

    def stack_init(pos: int, kind_pair):
        mixer_kind, ffn_kind = kind_pair

        def one(i):
            k = jax.random.fold_in(keys[2 + lead + pos], i)
            km, kf = jax.random.split(k)
            return {"mixer": _init_mixer(km, mixer_kind, cfg, tp, dtype,
                                         par.fuse_w13),
                    "ffn": _init_ffn(kf, ffn_kind, cfg, ep, tp, dtype,
                                     par.fuse_w13)}

        # vmapped over the repetition index: the same values as stacking
        # per-layer trees, without holding every layer twice
        return jax.vmap(one)(jnp.arange(reps))

    params["periods"] = [stack_init(i, kp) for i, kp in enumerate(period)]

    if cfg.mtp_depth:
        params["mtp"] = {
            "mixer": _init_mixer(keys[-2], period[-1][0], cfg, tp, dtype,
                                 par.fuse_w13),
            "ffn": _init_ffn(keys[-2], DENSE_FFN, cfg, ep, tp, dtype,
                             par.fuse_w13),
            "proj": (jax.random.normal(keys[-1], (2 * cfg.d_model, cfg.d_model))
                     * (2 * cfg.d_model) ** -0.5).astype(dtype),
        }
    if not cfg.tie_embeddings:
        # drawn last, from the embedding's key folded with 1, so every
        # earlier draw (and a tied model's whole tree) is unchanged
        params["head"] = iu.zero_pad_rows(
            jax.random.normal(jax.random.fold_in(keys[0], 1),
                              (cfg.vocab_size, cfg.d_model))
            * cfg.d_model ** -0.5, v_pad).astype(dtype)
    return params


def _layer_spec(kind_pair, cfg: ModelConfig, par: ParallelConfig,
                params_example: Dict) -> Dict:
    mixer_kind, ffn_kind = kind_pair
    ep_axes = _ep_axes(cfg, par)
    mix = _specs_for(params_example["mixer"], _MIXER_SPECS[mixer_kind])
    if ffn_kind == MOE_FFN:
        f = _specs_for(params_example["ffn"], _moe_specs(ep_axes))
    else:
        f = _specs_for(params_example["ffn"], _FFN_SPECS[ffn_kind])
    return {"mixer": mix, "ffn": f}


def param_specs(cfg: ModelConfig, par: ParallelConfig,
                params: Dict) -> Dict:
    """PartitionSpec tree matching ``init_model`` output (params may be a
    tree of ShapeDtypeStructs from jax.eval_shape)."""
    pat = expanded_pattern(cfg)
    lead = cfg.leading_dense_layers
    specs: Dict[str, Any] = {
        "embed": P("model", None),
        "final_norm": P(None),
    }
    if "head" in params:
        specs["head"] = P("model", None)
    if lead:
        specs["lead"] = [
            _layer_spec(pat[i], cfg, par, params["lead"][i])
            for i in range(lead)]
    specs["periods"] = []
    for pos, kp in enumerate(cfg.pattern):
        ex = params["periods"][pos]
        s = _layer_spec(kp, cfg, par, ex)
        # stacked leading (period) dim
        s = jax.tree.map(
            lambda sp: P(*([None] + list(sp))), s,
            is_leaf=lambda x: isinstance(x, P))
        specs["periods"].append(s)
    if cfg.mtp_depth and "mtp" in params:
        s = _layer_spec((cfg.pattern[-1][0], DENSE_FFN), cfg, par,
                        params["mtp"])
        s["proj"] = P(None, None)
        specs["mtp"] = s
    if par.zero3:
        flags = zero3_flags(cfg, par)

        def apply_z3(spec, flag, stacked):
            if not flag:
                return spec
            parts = list(spec)
            parts[1 if stacked else 0] = "data"
            return P(*parts)

        specs["periods"] = [
            jax.tree.map(lambda sp, fl: apply_z3(sp, fl, True), s_, f_,
                         is_leaf=lambda x: isinstance(x, P))
            for s_, f_ in zip(specs["periods"], flags["periods"])]
        if lead:
            specs["lead"] = [
                jax.tree.map(lambda sp, fl: apply_z3(sp, fl, False), s_, f_,
                             is_leaf=lambda x: isinstance(x, P))
                for s_, f_ in zip(specs["lead"], flags["lead"])]
    return specs


def _ep_axes(cfg: ModelConfig, par: ParallelConfig) -> Tuple[str, ...]:
    if cfg.moe is None:
        return ()
    if par.ep > 1:                   # dedicated first-class EP mesh axis
        return ("ep",)
    return ("data", "model") if par.ep_over_dp else ("model",)


def _ep_size(cfg: ModelConfig, par: ParallelConfig) -> int:
    if cfg.moe is None:
        return 1
    if par.ep > 1:
        return par.ep
    return par.dp * par.tp if par.ep_over_dp else par.tp


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def _maybe_gather_zero3(lp: Dict, par: ParallelConfig, flags=None,
                        dp_axis: str = "data"):
    """All-gather the ZeRO-3-sharded leaves over the data axis before use
    (the paper §2.1's easily-overlapped weight AllGather; XLA's latency
    hiding prefetches it across the scan step boundary)."""
    if not par.zero3 or flags is None:
        return lp

    def gather(w, flag):
        if flag:
            # ZeRO-3 weight gather over the DATA axis (not a TP seam)
            return lax.all_gather(  # lint: allow(raw-collective)
                w, dp_axis, axis=0, tiled=True)
        return w

    return jax.tree.map(gather, lp, flags)


def _apply_mixer(kind: str, p: Dict, x: Array, ctx: TPContext,
                 cfg: ModelConfig, collect_cache: bool = False):
    if kind == ATTN:
        return attention.gqa_train(p, x, ctx, cfg)
    if kind == MLA:
        return attention.mla_train(p, x, ctx, cfg)
    if kind == MAMBA:
        return mamba.mamba_train(p, x, ctx, cfg)
    if kind == RWKV:
        return rwkv.rwkv_time_train(p, x, ctx, cfg)
    raise ValueError(kind)


def _apply_ffn(kind: str, p: Dict, x: Array, ctx: TPContext,
               cfg: ModelConfig):
    if kind == DENSE_FFN:
        return ffn.ffn_train(p, x, ctx, cfg.norm_eps), 0.0
    if kind == MOE_FFN:
        return ffn.moe_train(p, x, ctx, cfg, cfg.norm_eps)
    if kind == RWKV:
        return rwkv.rwkv_channel_train(p, x, ctx, cfg), 0.0
    raise ValueError(kind)


def _block(kind_pair, lp: Dict, x: Array, ctx: TPContext, cfg: ModelConfig,
           par: ParallelConfig, z3=None,
           layer: Optional[int] = None) -> Tuple[Array, Array]:
    lp = _maybe_gather_zero3(lp, par, z3)
    ctx = ctx.with_layer(layer)        # per-layer plan overrides resolve here
    mixer_kind, ffn_kind = kind_pair
    x = x + _apply_mixer(mixer_kind, lp["mixer"], x, ctx, cfg)
    dy, aux = _apply_ffn(ffn_kind, lp["ffn"], x, ctx, cfg)
    return x + dy, jnp.asarray(aux, jnp.float32)


def backbone(params: Dict, x: Array, ctx: TPContext, cfg: ModelConfig,
             par: ParallelConfig) -> Tuple[Array, Array]:
    """x: [B, S/TP, D] -> (hidden [B, S/TP, D], aux_loss).  Replicated
    layout (``ctx.seq_sharded`` False): [B, S, D] -> [B, S, D] — the same
    seams run with hidden scatter and every between-seam op (norm,
    residual, shift, RoPE offsets) sees the full sequence."""
    pat = expanded_pattern(cfg)
    z3 = zero3_flags(cfg, par)
    lead = cfg.leading_dense_layers
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(lead):
        x, aux = _block(pat[i], params["lead"][i], x, ctx, cfg, par,
                        z3["lead"][i] if z3["lead"] else None, layer=i)
        aux_total = aux_total + aux

    def block_with_flags(pos, lp, x):
        flags = z3["periods"][pos] if z3["periods"] else None
        # scanned periods share one trace: the layer slot is the PATTERN
        # position (offset past the unrolled lead), not the repetition index
        return _block(cfg.pattern[pos], lp, x, ctx, cfg, par, flags,
                      layer=lead + pos)

    remat_block = jax.checkpoint(
        block_with_flags, static_argnums=(0,)) if par.remat != "none" \
        else block_with_flags

    def period_body(carry, stacked):
        x, aux = carry
        for pos in range(len(cfg.pattern)):
            x, a = remat_block(pos, stacked[pos], x)
            aux = aux + a
        return (x, aux), None

    (x, aux_total), _ = lax.scan(period_body, (x, aux_total),
                                 tuple(params["periods"]))
    return x, aux_total


def forward_loss(params: Dict, batch: Dict, ctx: TPContext, cfg: ModelConfig,
                 par: ParallelConfig) -> Array:
    """Training loss (per-device mean; caller psums over DP).

    batch: tokens [B_loc, S] (replicated over TP; the embedding's
    combining collective produces the residual layout) or embeds in the
    residual layout — [B_loc, S/TP, D] sequence-sharded (default) or
    [B_loc, S, D] replicated, per ``ctx.seq_sharded``
    (``sharding.activation_spec``); labels [B_loc, S] (full sequence)."""
    v_pad = pad_vocab(cfg.vocab_size, par.tp)
    if "embeds" in batch:
        x = batch["embeds"]
    else:
        x = layers.embed_lookup(params["embed"], batch["tokens"], ctx, v_pad)
    x = x.astype(cfg.compute_dtype)

    h, aux = backbone(params, x, ctx, cfg, par)
    h = layers.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = layers.lm_head_logits(h, layers.head_table(params),
                                   ctx)                      # [B, S, V/TP]

    labels = batch["labels"]
    ce = layers.vocab_parallel_xent(logits, labels, ctx, v_pad,
                                    cfg.vocab_size)  # [B, S]
    mask = (labels >= 0) & (labels < cfg.vocab_size)
    loss = jnp.sum(jnp.where(mask, ce, 0)) / jnp.maximum(jnp.sum(mask), 1)

    if cfg.mtp_depth and "mtp" in params:
        loss = loss + 0.3 * _mtp_loss(params, h, batch, ctx, cfg, par, v_pad)
    if cfg.moe is not None:
        loss = loss + 0.01 * aux
    return loss


def _mtp_loss(params, h, batch, ctx, cfg, par, v_pad):
    """DeepSeek multi-token prediction: one extra block predicts t+2 from the
    final hidden state fused with the (shifted) next-token embedding."""
    mtp = params["mtp"]
    if "embeds" in batch:
        nxt = batch["embeds"]
    else:
        nxt = layers.embed_lookup(params["embed"], batch["tokens"], ctx, v_pad)
    nxt = layers.shift_tokens_left(nxt.astype(h.dtype), ctx)  # emb of t+1
    fused = jnp.concatenate([h, nxt], axis=-1)
    x = jnp.einsum("bsd,dm->bsm", fused, mtp["proj"])
    x, _ = _block((cfg.pattern[-1][0], DENSE_FFN),
                  {"mixer": mtp["mixer"], "ffn": mtp["ffn"]}, x, ctx, cfg, par)
    logits = layers.lm_head_logits(x, layers.head_table(params), ctx)
    # labels shifted one extra step
    labels = batch["labels"]
    lab2 = jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
    ce = layers.vocab_parallel_xent(logits, lab2, ctx, v_pad,
                                    cfg.vocab_size)
    mask = (lab2 >= 0) & (lab2 < cfg.vocab_size)
    return jnp.sum(jnp.where(mask, ce, 0)) / jnp.maximum(jnp.sum(mask), 1)


def count_params_analytic(cfg: ModelConfig, active_only: bool = False,
                          par: Optional[ParallelConfig] = None) -> int:
    """Exact parameter count via eval_shape of init (no allocation).
    ``active_only`` scales routed-expert weights by top_k/num_experts
    (MODEL_FLOPS = 6·N_active·D for MoE)."""
    par = par or ParallelConfig(tp=1, dp=1)
    shapes = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg, par))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [str(getattr(p, "key", "")) for p in path]
        n = 1
        for d in leaf.shape:
            n *= d
        # routed experts carry an expert dim: 3-D (or 4-D when period-stacked)
        is_expert = (cfg.moe is not None and "ffn" in names
                     and "shared" not in names
                     and any(k in names for k in ("w1", "w2", "w3"))
                     and leaf.ndim >= 3)
        if active_only and is_expert:
            n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total
