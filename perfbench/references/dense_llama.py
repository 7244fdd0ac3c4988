"""Plain reference of a dense decoder: GQA attention with rotary positions,
SwiGLU FFN, RMSNorm, tied embeddings.  It imports nothing of the program.

Weights are made here from the seed, as random normals scaled by
1/sqrt(fan_in) and rounded to bf16 (the configuration's stated weight
type), drawn from the seed's key in this order: the key splits into
``layers + 4``; the embedding is drawn from the first; layer ``i`` from the
third folded with ``i``, split into an attention key (four draws: q, k, v,
o) and an FFN key (three draws: gate, up, down); norm scales are ones.
That is the seed-to-weights map the served program states for its random
weights; the program's own layout (packing, padding) plays no part.

Everything is computed in float32 with the matmuls at ``HIGHEST``
precision, layer by layer, so only one layer's weights are held.  With
``fp8=True`` every matmul's operands are first rounded to float8 (e4m3,
one scale per tensor; in training the gradients flowing back through them
to e5m2, one scale per tensor): the control, one precision step below the
bf16 that the configuration states.

``train`` runs the first steps of training from the same weights: mean
next-token cross-entropy, global gradient-norm clipping and AdamW, with the
weights kept in bf16 between steps as the configuration states and the
moments in float32.  It reports each step's loss, each tensor's norm of
the first (clipped) gradient, and each tensor's norm of the change after
the last step.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def dims(model: Dict) -> Dict:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return dict(L=model["num_hidden_layers"], D=d, H=h,
                Hkv=model["num_key_value_heads"],
                dh=model.get("head_dim") or d // h,
                F=model["intermediate_size"], V=model["vocab_size"],
                eps=model["rms_norm_eps"], theta=model["rope_theta"],
                rot=model.get("partial_rotary_factor", 1.0))


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


E5M2_MAX = 57344.0


def _round(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, ct):
    return (_round(ct, jnp.float8_e5m2, E5M2_MAX),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: [S, heads, dh]; rotate the two halves of each head."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_weights(key, d: Dict) -> Dict:
    D, H, Hkv, dh, F = d["D"], d["H"], d["Hkv"], d["dh"], d["F"]
    km, kf = jax.random.split(key)
    k1, k2, k3, k4 = jax.random.split(km, 4)
    f1, f2, f3 = jax.random.split(kf, 3)
    n = jax.random.normal
    s = D ** -0.5
    return {"wq": _bf16(n(k1, (D, H * dh)) * s),
            "wk": _bf16(n(k2, (D, Hkv * dh)) * s),
            "wv": _bf16(n(k3, (D, Hkv * dh)) * s),
            "wo": _bf16(n(k4, (H * dh, D)) * s),
            "w1": _bf16(n(f1, (D, F)) * s),
            "w3": _bf16(n(f2, (D, F)) * s),
            "w2": _bf16(n(f3, (F, D)) * F ** -0.5)}


def _layer(x, key, d: Dict, fp8: bool):
    """x: [B, S, D] float32, each row a causal sequence from position 0."""
    w = layer_weights(key, d)
    H, Hkv, dh = d["H"], d["Hkv"], d["dh"]
    S = x.shape[1]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]

    def attend(xs):
        h = _rms(xs, d["eps"])
        q = _mm(h, w["wq"], fp8).reshape(S, H, dh)
        k = _mm(h, w["wk"], fp8).reshape(S, Hkv, dh)
        v = _mm(h, w["wv"], fp8).reshape(S, Hkv, dh)
        q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
        group = H // Hkv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        if fp8:
            q, k, v = _fp8(q), _fp8(k), _fp8(v)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        if fp8:
            p = _fp8(p)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
        return xs + _mm(o.reshape(S, H * dh), w["wo"], fp8)

    x = jax.lax.map(attend, x)
    h = _rms(x, d["eps"])
    y = jax.nn.silu(_mm(h, w["w1"], fp8)) * _mm(h, w["w3"], fp8)
    return x + _mm(y, w["w2"], fp8)


def embedding(key, d: Dict):
    return (jax.random.normal(key, (d["V"], d["D"])) * d["D"] ** -0.5
            ).astype(jnp.bfloat16)


def logits(model: Dict, seed: int, seqs: Sequence[np.ndarray],
           rows: Sequence[Sequence[int]], s_max: int,
           fp8: bool = False):
    """Next-token logits [B, T, V] (float32, on the device) at positions
    ``rows[b]`` of each token sequence ``seqs[b]``; sequences are padded at
    the end to ``s_max`` (padding cannot reach an earlier position).  Rows
    beyond ``len(rows[b])`` repeat its last position."""
    d = dims(model)
    if d["rot"] != 1.0:
        raise NotImplementedError("partial rotary embeddings")
    B, T = len(seqs), max(len(r) for r in rows)
    toks = np.zeros((B, s_max), np.int32)
    sel = np.zeros((B, T), np.int32)
    for b, (s, r) in enumerate(zip(seqs, rows)):
        toks[b, :len(s)] = s
        sel[b] = list(r) + [r[-1]] * (T - len(r))
    keys = jax.random.split(jax.random.PRNGKey(seed), d["L"] + 4)
    emb = jax.jit(embedding, static_argnums=1)(keys[0], _Frozen(d))
    x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
    step = jax.jit(lambda x, k: _layer(x, k, d, fp8))
    for i in range(d["L"]):
        x = step(x, jax.random.fold_in(keys[2], i))

    @jax.jit
    def head(x, emb, sel):
        h = _rms(jnp.take_along_axis(x, sel[:, :, None], axis=1), d["eps"])
        return _mm(h, emb.astype(jnp.float32).T, fp8)

    return head(x, emb, jnp.asarray(sel))


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
#: the tensors ``train`` reports norms of; layer tensors are over all layers
TENSORS = ("embed", "final_norm", "attn_norm", "wq", "wk", "wv", "wo",
           "ffn_norm", "w1", "w3", "w2")


def init_train(key, d: Dict) -> Dict:
    """The weights drawn from the seed's ``key`` as ``logits`` draws them,
    in bf16, layer tensors stacked over the layers."""
    keys = jax.random.split(key, d["L"] + 4)
    layers = jax.vmap(lambda i: layer_weights(
        jax.random.fold_in(keys[2], i), d))(jnp.arange(d["L"]))
    p = {k: v.astype(jnp.bfloat16) for k, v in layers.items()}
    p["embed"] = embedding(keys[0], d)
    ones = jnp.ones((d["D"],), jnp.bfloat16)
    p["final_norm"] = ones
    p["attn_norm"] = jnp.tile(ones, (d["L"], 1))
    p["ffn_norm"] = jnp.tile(ones, (d["L"], 1))
    return p


def _train_layer(x, w, d: Dict, fp8: bool):
    """x: [B, S, D] float32; ``w`` one layer's tensors (float32)."""
    B, S, _ = x.shape
    H, Hkv, dh = d["H"], d["Hkv"], d["dh"]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    h = _rms(x, d["eps"]) * w["attn_norm"]
    q = _mm(h, w["wq"], fp8).reshape(B, S, H, dh)
    k = _mm(h, w["wk"], fp8).reshape(B, S, Hkv, dh)
    v = _mm(h, w["wv"], fp8).reshape(B, S, Hkv, dh)
    rope = jax.vmap(lambda t: _rope(t, pos, d["theta"]))
    q, k = rope(q), rope(k)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    if fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * dh ** -0.5
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
    x = x + _mm(o.reshape(B, S, H * dh), w["wo"], fp8)
    h = _rms(x, d["eps"]) * w["ffn_norm"]
    y = jax.nn.silu(_mm(h, w["w1"], fp8)) * _mm(h, w["w3"], fp8)
    return x + _mm(y, w["w2"], fp8)


def _loss(p, tokens, labels, d: Dict, fp8: bool):
    f32 = lambda t: t.astype(jnp.float32)
    emb = f32(p["embed"])
    x = jnp.take(emb, tokens, axis=0)
    stacked = {k: p[k] for k in TENSORS[2:]}
    layer = jax.checkpoint(lambda x, w: _train_layer(
        x, jax.tree.map(f32, w), d, fp8))
    x, _ = jax.lax.scan(lambda x, w: (layer(x, w), None), x, stacked)
    h = _rms(x, d["eps"]) * f32(p["final_norm"])
    z = _mm(h, emb.T, fp8)
    lse = jax.nn.logsumexp(z, axis=-1)
    tgt = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def _sharding(mesh, x):
    """Shard the last axis over the devices where it divides, else the
    one before it, else keep the tensor whole."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size
    spec = [None] * x.ndim
    for ax in (x.ndim - 1, x.ndim - 2):
        if ax >= 0 and x.shape[ax] % n == 0:
            spec[ax] = "x"
            break
    return NamedSharding(mesh, P(*spec))


def make_step(d: Dict, opt: Dict, fp8: bool = False):
    """The jitted AdamW step: (params, mu, nu, tokens, labels, count) ->
    (params, mu, nu, loss, each tensor's norm of the clipped gradient)."""
    norms = lambda t: {k: jnp.linalg.norm(v.astype(jnp.float32))
                       for k, v in t.items()}

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mu, nu, tokens, labels, count):
        loss, g = jax.value_and_grad(_loss)(p, tokens, labels, _Frozen(d),
                                            fp8)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["grad_clip"] / (gnorm + 1e-6)), g)
        c1 = 1.0 - opt["b1"] ** count
        c2 = 1.0 - opt["b2"] ** count

        def upd(p, g, m, v):
            m = opt["b1"] * m + (1 - opt["b1"]) * g
            v = opt["b2"] * v + (1 - opt["b2"]) * g * g
            p32 = p.astype(jnp.float32)
            p32 = p32 - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2)
                                                 + opt["eps"])
                                     + opt["weight_decay"] * p32)
            return p32.astype(p.dtype), m, v
        out = {k: upd(params_k, g[k], mu[k], nu[k])
               for k, params_k in p.items()}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()}, loss, norms(g))
    return step


def train(model: Dict, seed: int, batches: Sequence[Dict], opt: Dict,
          devices, fp8: bool = False) -> Dict:
    """``len(batches)`` steps of AdamW from the seed's weights.  ``opt``:
    ``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay``, ``grad_clip``.
    Returns ``loss`` (one per step), ``grad`` (each tensor's norm of the
    first step's clipped gradient) and ``change`` (each tensor's norm of
    the weights' change over all the steps)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    d = dims(model)
    if d["rot"] != 1.0:
        raise NotImplementedError("partial rotary embeddings")
    mesh = Mesh(np.asarray(devices), ("x",))
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(lambda k: init_train(k, _Frozen(d)), key)
    shard = jax.tree.map(lambda x: _sharding(mesh, x), shapes)
    init = jax.jit(init_train, static_argnums=1, out_shardings=shard)
    params = init(key, _Frozen(d))
    zeros = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), p), out_shardings=shard)
    mu, nu = zeros(params), zeros(params)
    rows = NamedSharding(mesh, P("x" if len(batches[0]["tokens"])
                                 % len(devices) == 0 else None, None))
    step = make_step(d, opt, fp8)

    losses, first = [], None
    for i, b in enumerate(batches):
        tokens = jax.device_put(np.asarray(b["tokens"], np.int32), rows)
        labels = jax.device_put(np.asarray(b["labels"], np.int32), rows)
        params, mu, nu, loss, gn = step(params, mu, nu, tokens, labels,
                                        jnp.float32(i + 1))
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in gn.items()}
    del mu, nu
    p0 = init(key, _Frozen(d))
    change = jax.jit(lambda a, b: {k: jnp.linalg.norm(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)) for k in a})
    out = {"loss": losses, "grad": first,
           "change": {k: float(v) for k, v in change(params, p0).items()}}
    del params, p0
    return out


class _Frozen(dict):
    """A hashable view of the dimension dict (a static jit argument)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
