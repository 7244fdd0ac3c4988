"""Plain reference of a DeepSeek-V2 decoder: multi-head latent attention
without query compression, YaRN rotary positions, routed experts under
softmax top-k gates with shared experts, RMSNorm, an untied LM head.  It
imports nothing of the program.

Weights are made here from the seed, as random normals scaled by
1/sqrt(fan_in) and rounded to bf16 (the router stays float32), in the
order the served program states for its random weights: the seed's key
splits into ``layers + 4``; the embedding is drawn from the first, the LM
head from the first folded with 1; a leading dense layer ``i`` from key
``1 + i`` (attention and FFN alike), the expert layer ``j`` after them from
key ``2 + dense layers`` folded with ``j``, split into an attention key and
an FFN key.  An attention key splits in six: the query from the first, the
latent down-projection from the third, its up-projection from the fourth,
the output from the fifth.  A dense FFN key splits in three (gate, up,
down); an expert FFN key in five: the router, the routed experts' gate, up
and down stacks, and the shared experts, drawn as one dense FFN of
``n_shared`` times the expert width.  Norm scales are ones.  Heads are laid
out head by head: each query head's columns are its non-rotated part, then
its rotated part; each head of the latent up-projection holds its key part,
then its value part.

Attention is in its published, non-absorbed form: keys and values are
expanded from the latent for every head.  The rotated halves pair column
``i`` with column ``i + d/2`` of each rotated part (the program's
convention); the published code pairs adjacent columns, so this is the
published model with the rotated columns of the query and of the latent
down-projection relabelled, which random weights do not see.  YaRN follows
DeepSeek-V2's published code, and the softmax scale carries its
temperature factor squared.  Each routed expert is computed only on the
tokens that chose it: the (token, expert) pairs are sorted by expert into
blocks of one expert each.

Everything is computed in float32 with the matmuls at ``HIGHEST``
precision, layer by layer, so only one layer's weights are held.  With
``fp8=True`` every matmul's operands are first rounded to float8 (e4m3,
one scale per tensor): the control, one precision step below the bf16 that
the configuration states.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
#: query rows per attention block (a scan over the blocks, each against
#: every key under the causal mask: one block to compile, not one per
#: length); rows of one expert per grouped block
Q_BLOCK = 1024
E_BLOCK = 256


def dims(model: Dict) -> Dict:
    if model.get("q_lora_rank") is not None:
        raise NotImplementedError("a compressed query (q_lora_rank)")
    if model.get("scoring_func", "softmax") != "softmax" or model.get(
            "topk_method", "greedy") != "greedy":
        raise NotImplementedError("routing other than softmax top-k")
    y = model.get("rope_scaling")
    if y is not None and y.get("type", y.get("rope_type")) != "yarn":
        raise NotImplementedError(f"rope_scaling {y}")
    return dict(L=model["num_hidden_layers"], D=model["hidden_size"],
                H=model["num_attention_heads"], R=model["kv_lora_rank"],
                dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"],
                dv=model["v_head_dim"], F=model["intermediate_size"],
                Fe=model["moe_intermediate_size"],
                E=model["n_routed_experts"], K=model["num_experts_per_tok"],
                Ns=model.get("n_shared_experts") or 0,
                lead=model.get("first_k_dense_replace", 0),
                norm_topk=bool(model.get("norm_topk_prob", False)),
                route_scale=float(model.get("routed_scaling_factor", 1.0)),
                V=model["vocab_size"], eps=model["rms_norm_eps"],
                theta=model["rope_theta"],
                yarn=tuple(sorted(y.items())) if y else None)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _yarn_m(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, pos, d: Dict):
    """x: [S, heads, dr]; YaRN's frequencies where the model has them."""
    dr, theta = x.shape[-1], d["theta"]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    m = 1.0
    if d["yarn"] is not None:
        y = dict(d["yarn"])
        f, orig = y["factor"], y["original_max_position_embeddings"]

        def slot(turns):
            return dr * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))
        low = max(math.floor(slot(y["beta_fast"])), 0)
        high = min(math.ceil(slot(y["beta_slow"])), dr - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / f * ramp + inv * (1.0 - ramp)
        m = _yarn_m(f, y.get("mscale", 1.0)) / _yarn_m(
            f, y.get("mscale_all_dim", 0.0))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(d: Dict) -> float:
    scale = (d["dn"] + d["dr"]) ** -0.5
    if d["yarn"] is not None:
        y = dict(d["yarn"])
        if y.get("mscale_all_dim"):
            scale *= _yarn_m(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _ffn_weights(key, D, F):
    f1, f2, f3 = jax.random.split(key, 3)
    n = jax.random.normal
    return {"w1": _bf16(n(f1, (D, F)) * D ** -0.5),
            "w3": _bf16(n(f2, (D, F)) * D ** -0.5),
            "w2": _bf16(n(f3, (F, D)) * F ** -0.5)}


def attn_weights(key, d: Dict) -> Dict:
    D, H, R = d["D"], d["H"], d["R"]
    ks = jax.random.split(key, 6)
    n = jax.random.normal
    return {"wq": _bf16(n(ks[0], (D, H * (d["dn"] + d["dr"]))) * D ** -0.5),
            "wdkv": _bf16(n(ks[2], (D, R + d["dr"])) * D ** -0.5),
            "wukv": _bf16(n(ks[3], (R, H * (d["dn"] + d["dv"])))
                          * R ** -0.5),
            "wo": _bf16(n(ks[4], (H * d["dv"], D)) * D ** -0.5)}


def expert_weights(key, d: Dict) -> Dict:
    D, E, Fe = d["D"], d["E"], d["Fe"]
    ks = jax.random.split(key, 5)
    n = jax.random.normal
    w = {"router": n(ks[0], (D, E)) * D ** -0.5,
         "w1": _bf16(n(ks[1], (E, D, Fe)) * D ** -0.5),
         "w3": _bf16(n(ks[2], (E, D, Fe)) * D ** -0.5),
         "w2": _bf16(n(ks[3], (E, Fe, D)) * Fe ** -0.5)}
    if d["Ns"]:
        w["shared"] = _ffn_weights(ks[4], D, d["Ns"] * Fe)
    return w


def _attend(x, w, d: Dict, fp8: bool):
    """One sequence [S, D] (a causal sequence from position 0) through the
    attention block, residual included."""
    S = x.shape[0]
    H, R, dn, dr, dv = d["H"], d["R"], d["dn"], d["dr"], d["dv"]
    pos = jnp.arange(S)
    h = _rms(x, d["eps"])
    q = _mm(h, w["wq"], fp8).reshape(S, H, dn + dr)
    kv = _mm(h, w["wdkv"], fp8)
    c = _rms(kv[:, :R], d["eps"])
    k_rope = _rope(kv[:, None, R:], pos, d)                 # [S, 1, dr]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, d)], -1)
    kvu = _mm(c, w["wukv"], fp8).reshape(S, H, dn + dv)
    k = jnp.concatenate([kvu[..., :dn],
                         jnp.broadcast_to(k_rope, (S, H, dr))], -1)
    v = kvu[..., dn:]
    if fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    qb = min(Q_BLOCK, S)

    def block(_, i):                   # causal: a mask over every key
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        sc = jnp.einsum("qhd,khd->hqk", qi, k,
                        precision=HI) * softmax_scale(d)
        mask = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        if fp8:
            p = _fp8(p)
        return None, jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    _, outs = jax.lax.scan(block, None, jnp.arange(S // qb))
    o = outs.reshape(S, H * dv)
    return x + _mm(o, w["wo"], fp8)


def _swiglu(h, w, fp8: bool):
    y = jax.nn.silu(_mm(h, w["w1"], fp8)) * _mm(h, w["w3"], fp8)
    return _mm(y, w["w2"], fp8)


def _routed(h, w, d: Dict, fp8: bool):
    """The routed experts' output for tokens ``h`` [T, D]: each expert on
    the tokens that chose it alone, weighted by their gates."""
    T, D = h.shape
    E, K = d["E"], d["K"]
    probs = jax.nn.softmax(jnp.matmul(h, w["router"], precision=HI), -1)
    gate, eidx = jax.lax.top_k(probs, K)
    if d["norm_topk"]:
        gate = gate / jnp.sum(gate, -1, keepdims=True)
    else:
        gate = gate * d["route_scale"]
    flat = eidx.reshape(-1)
    n = flat.shape[0]
    order = jnp.argsort(flat, stable=True)
    fe, src, g = flat[order], order // K, gate.reshape(-1)[order]
    count = jnp.bincount(flat, length=E)
    padded = -(-count // E_BLOCK) * E_BLOCK
    first = jnp.cumsum(count) - count              # first sorted row
    end_pad = jnp.cumsum(padded)
    dest = end_pad[fe] - padded[fe] + jnp.arange(n) - first[fe]
    nb = -(-n // E_BLOCK) + E
    xs = jnp.zeros((nb * E_BLOCK, D), h.dtype).at[dest].set(h[src])
    blk_e = jnp.minimum(jnp.searchsorted(
        end_pad, jnp.arange(nb) * E_BLOCK, side="right"), E - 1)

    def block(_, b):
        e = blk_e[b]
        xb = jax.lax.dynamic_slice_in_dim(xs, b * E_BLOCK, E_BLOCK)
        return None, _swiglu(xb, {k: w[k][e] for k in ("w1", "w3", "w2")},
                             fp8)

    _, ys = jax.lax.scan(block, None, jnp.arange(nb))
    ys = ys.reshape(nb * E_BLOCK, D)
    return jnp.zeros((T, D), h.dtype).at[src].add(ys[dest] * g[:, None])


def _layer(x, key, d: Dict, dense: bool, fp8: bool):
    """x: [B, S, D] float32, each row a causal sequence from position 0."""
    if dense:
        wa, wf = attn_weights(key, d), _ffn_weights(key, d["D"], d["F"])
    else:
        ka, kf = jax.random.split(key)
        wa, wf = attn_weights(ka, d), expert_weights(kf, d)

    def one(xs):
        xs = _attend(xs, wa, d, fp8)
        h = _rms(xs, d["eps"])
        if dense:
            return xs + _swiglu(h, wf, fp8)
        y = _routed(h, wf, d, fp8)
        if d["Ns"]:
            y = y + _swiglu(h, wf["shared"], fp8)
        return xs + y

    return jax.lax.map(one, x)


def logits(model: Dict, seed: int, seqs: Sequence[np.ndarray],
           rows: Sequence[Sequence[int]], s_max: int,
           fp8: bool = False):
    """Next-token logits [B, T, V] (float32, on the device) at positions
    ``rows[b]`` of each token sequence ``seqs[b]``; sequences are padded at
    the end to a whole number of attention blocks, at most ``s_max`` and
    the same for all (padding cannot reach an earlier position).  Rows
    beyond ``len(rows[b])`` repeat its last position."""
    d = dims(model)
    longest = max(len(s) for s in seqs)
    qb = min(Q_BLOCK, -(-longest // 8) * 8)
    S = -(-longest // qb) * qb
    if S > max(s_max, qb):
        raise ValueError(f"sequence of {longest} tokens beyond {s_max}")
    B, T = len(seqs), max(len(r) for r in rows)
    toks = np.zeros((B, S), np.int32)
    sel = np.zeros((B, T), np.int32)
    for b, (s, r) in enumerate(zip(seqs, rows)):
        toks[b, :len(s)] = s
        sel[b] = list(r) + [r[-1]] * (T - len(r))
    keys = jax.random.split(jax.random.PRNGKey(seed), d["L"] + 4)
    table = jax.jit(lambda k: _bf16(
        jax.random.normal(k, (d["V"], d["D"])) * d["D"] ** -0.5))
    x = jnp.take(table(keys[0]), jnp.asarray(toks), axis=0)
    frozen = _Frozen(d)
    step = jax.jit(_layer, static_argnums=(2, 3, 4))
    for i in range(d["L"]):
        if i < d["lead"]:
            x = step(x, keys[1 + i], frozen, True, fp8)
        else:
            x = step(x, jax.random.fold_in(keys[2 + d["lead"]], i - d["lead"]),
                     frozen, False, fp8)

    @jax.jit
    def head(x, table, sel):
        h = _rms(jnp.take_along_axis(x, sel[:, :, None], axis=1), d["eps"])
        return _mm(h, table.T, fp8)

    return head(x, table(jax.random.fold_in(keys[0], 1)), jnp.asarray(sel))


class _Frozen(dict):
    """A hashable view of the dimension dict (a static jit argument)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
