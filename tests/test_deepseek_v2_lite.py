"""DeepSeek-V2-Lite's parts of the program: latent attention without query
compression, YaRN, the dropless serving MoE (and its share per chip), the
untied head, and the tied models' weights left as they were."""
import dataclasses
import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.configs.base import ParallelConfig, get_config, get_smoke_config
from repro.models import attention, ffn, layers
from repro.models import model as M
from repro.parallel.sharding import TPContext
from repro.runtime.server import Request, ServeConfig, Server


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def test_yarn_frequencies_and_scale_follow_the_published_formula():
    cfg = get_config("deepseek_v2_lite")
    y, d, theta = cfg.rope_scaling, cfg.mla.qk_rope_head_dim, cfg.rope_theta
    assert d == 64
    assert layers.yarn_range(d, theta, y) == (10, 23)

    def corr(turns):
        return d * math.log(y.original_max_position_embeddings
                            / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), d - 1)
    i = np.arange(d // 2)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = theta ** (-2 * i / d) * (ramp / y.factor + (1 - ramp))
    got = np.asarray(layers.rope_freqs(d, theta, y))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # extrapolated below the ramp, interpolated by the factor above it
    np.testing.assert_allclose(got[:low], theta ** (-2 * i[:low] / d),
                               rtol=2e-6)
    np.testing.assert_allclose(got[high:] * y.factor,
                               theta ** (-2 * i[high:] / d), rtol=2e-6)

    m = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1
    assert round(m * m, 3) == 1.590
    assert attention._mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    # cos and sin keep their size: mscale == mscale_all_dim
    x = jnp.ones((1, 3, 1, d))
    pos = jnp.array([[0, 1, 4000]])
    norms = jnp.linalg.norm(layers.apply_rope(x, pos, theta, y), axis=-1)
    np.testing.assert_allclose(np.asarray(norms), math.sqrt(d), rtol=1e-5)


# ---------------------------------------------------------------------------
# Dropless serving MoE
# ---------------------------------------------------------------------------
def _moe_cfg(num_experts=32, top_k=2, shared=1):
    cfg = get_smoke_config("deepseek_v2_lite")
    return dataclasses.replace(cfg, d_model=64, moe=dataclasses.replace(
        cfg.moe, num_experts=num_experts, top_k=top_k, expert_ffn=32,
        num_shared_experts=shared, shared_ffn=32 if shared else 0))


def _per_token(p, x, cfg):
    """Each token through its own top-k experts and the shared ones, one
    token at a time, in float32."""
    f32 = lambda a: np.asarray(a, np.float32)
    mc = cfg.moe
    h = f32(layers.rms_norm(jnp.asarray(x), p["norm"], cfg.norm_eps))
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        logits = h[t] @ f32(p["router"])
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:mc.top_k]
        gates = probs[top] / (probs[top].sum() if mc.norm_topk else 1.0)
        for e, g in zip(top, gates):
            a = h[t] @ f32(p["w1"][e])
            y = (a / (1 + np.exp(-a))) * (h[t] @ f32(p["w3"][e]))
            out[t] += g * (y @ f32(p["w2"][e]))
        if mc.num_shared_experts:
            a = h[t] @ f32(p["shared"]["w1"])
            y = (a / (1 + np.exp(-a))) * (h[t] @ f32(p["shared"]["w3"]))
            out[t] += y @ f32(p["shared"]["w2"])
    return out


def _serve_moe(p, x, cfg):
    mesh = _mesh()
    ctx = TPContext(axis="model", dp_axes=(), ep_axes=("model",))
    specs = M._specs_for(p, M._moe_specs(("model",)))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(specs, P()),
                       out_specs=P(), check_vma=False)
    def run(p, x):
        return ffn.moe_serve(p, x, ctx, cfg)
    return np.asarray(run(p, x), np.float32)


def test_serving_moe_drops_nothing_when_every_token_picks_one_expert():
    """Every token routes to expert 3 first (a capacity of 8x the mean
    load would keep 64 of the 128 tokens) and each token still gets its own
    output: every held expert runs on every token."""
    cfg = _moe_cfg()
    p = ffn.init_moe(jax.random.PRNGKey(0), cfg, 1, 1, jnp.float32)
    p["router"] = p["router"].at[:, 3].set(0.0).at[0, 3].set(2.0)
    t = 128
    x = jax.random.normal(jax.random.PRNGKey(1), (t, cfg.d_model))
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 3.0)      # every token: expert 3
    h = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    _, gate, eidx = ffn.route(p, h, cfg.moe)
    assert np.all(np.asarray(eidx)[:, 0] == 3)
    assert max(32, t * cfg.moe.top_k * 8 // cfg.moe.num_experts) < t
    assert not cfg.moe.norm_topk and float(jnp.max(jnp.sum(gate, -1))) < 1
    got = _serve_moe(p, x[None], cfg)[0]
    want = _per_token(p, np.asarray(x), cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rows,cols,norm_topk", [
    (1, 40, True), (1, 40, False), (8, 1, False)])
def test_serving_moe_matches_a_per_token_loop(rows, cols, norm_topk):
    """The prefill chunk's [1, C, D] and the decode step's [B, 1, D], with
    and without renormalised gates, give each token the per-token loop's
    output."""
    cfg = _moe_cfg(num_experts=8, top_k=6 if rows > 1 else 3,
                   shared=0 if norm_topk else 1)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, norm_topk=norm_topk))
    p = ffn.init_moe(jax.random.PRNGKey(2), cfg, 1, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, cols, cfg.d_model))
    got = _serve_moe(p, x, cfg).reshape(rows * cols, -1)
    want = _per_token(p, np.asarray(x).reshape(rows * cols, -1), cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


CHIP_SHARE = r"""
import dataclasses, functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map
from repro.configs.base import get_smoke_config
from repro.models import ffn, layers
from repro.models import model as M
from repro.parallel.sharding import TPContext

cfg = get_smoke_config("deepseek_v2_lite")
cfg = dataclasses.replace(cfg, d_model=64, moe=dataclasses.replace(
    cfg.moe, num_experts=16, top_k=6, expert_ffn=32, shared_ffn=32))
p = ffn.init_moe(jax.random.PRNGKey(0), cfg, 4, 4, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (1, 48, cfg.d_model))

def serve(n):
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(1, n), ("data", "model"))
    ctx = TPContext(axis="model", dp_axes=(), ep_axes=("model",))
    specs = M._specs_for(p, M._moe_specs(("model",)))
    def parts(p, x):
        # this chip's experts' part of the routed output, before the combine
        h = layers.rms_norm(x, p["norm"], cfg.norm_eps).reshape(-1, 64)
        _, gate, eidx = ffn.route(p, h, cfg.moe)
        e_loc = p["w1"].shape[0]
        held = ffn.experts_held(p, h, gate, eidx,
                                jax.lax.axis_index("model") * e_loc)
        return held[None], ffn.moe_serve(p, x, ctx, cfg)
    f = jax.jit(shard_map(parts, mesh=mesh, in_specs=(specs, P()),
                          out_specs=(P("model"), P()), check_vma=False))
    return f(p, x)

parts4, whole4 = serve(4)
parts1, whole1 = serve(1)
assert parts4.shape[0] == 4 and parts1.shape[0] == 1
# the shared experts, counted once
sh = {"norm": p["norm"], **p["shared"]}
ctx1 = TPContext()
shared = np.asarray(ffn.ffn_decode(sh, x, ctx1, cfg.norm_eps))[0]
routed = np.asarray(parts4).sum(0)
np.testing.assert_allclose(routed, np.asarray(parts1)[0], rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(routed + shared, np.asarray(whole1)[0],
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(whole4), np.asarray(whole1),
                           rtol=1e-4, atol=1e-4)
# every chip's part is its own: none of them is the whole
assert all(np.abs(np.asarray(q) - routed).max() > 1e-3 for q in parts4)
print("OK")
"""


def test_four_chips_expert_parts_sum_to_the_uncut_layer(subproc):
    """On four CPU devices each chip holds 4 of the 16 experts: the parts
    of the routed output that the four give sum, with the shared experts
    counted once, to the layer's output on one device; the served layer
    (its psum) gives the same."""
    assert "OK" in subproc(CHIP_SHARE)


# ---------------------------------------------------------------------------
# Latent attention without query compression, through the Server
# ---------------------------------------------------------------------------
def test_mla_without_query_compression_builds_one_query_projection():
    cfg = get_smoke_config("deepseek_v2_lite")
    assert cfg.mla.q_lora_rank is None
    p = attention.init_mla(jax.random.PRNGKey(0), cfg, 4)
    assert "w_q" in p and not {"w_dq", "w_uq", "q_norm"} & set(p)
    m = cfg.mla
    assert p["w_q"].shape == (cfg.d_model, cfg.num_heads
                              * (m.qk_nope_head_dim + m.qk_rope_head_dim))
    assert M._MIXER_SPECS["mla"]["w_q"] == P(None, "model")


def test_prefill_then_paged_decode_matches_the_full_forward():
    """Chunked prefill and paged decode (absorbed latent attention, the
    serving MoE) give, at every served position, the logits of the
    training forward (non-absorbed attention over the whole sequence)."""
    cfg = get_smoke_config("deepseek_v2_lite")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=64.0))       # the training forward drops none
    par = ParallelConfig(tp=1, dp=1)
    mesh = _mesh()
    params = M.init_model(jax.random.PRNGKey(3), cfg, par)
    sc = ServeConfig(max_batch=2, max_seq=64, eos_token=-1, max_new_tokens=6,
                     block_size=8, prefill_chunk=8, keep_logits=True)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (19,), 0,
                                           cfg.vocab_size), np.int32)
    req = Request(rid=0, prompt=prompt)
    Server(cfg, par, mesh, params, sc).serve([req])
    seq = np.concatenate([prompt, np.asarray(req.output[:-1], np.int32)])

    specs = M.param_specs(cfg, par, params)
    ctx = TPContext(axis="model", dp_axes=("data",), ep_axes=("model",))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(specs, P()),
                       out_specs=P(), check_vma=False)
    def full(p, tokens):
        x = layers.embed_lookup(p["embed"], tokens, ctx, cfg.vocab_size)
        h, _ = M.backbone(p, x.astype(cfg.compute_dtype), ctx, cfg, par)
        h = layers.rms_norm(h, p["final_norm"], cfg.norm_eps)
        return jnp.einsum("bsd,vd->bsv", h, layers.head_table(p))

    want = np.asarray(full(params, jnp.asarray(seq[None])), np.float32)[0]
    got = np.stack(req.logits)
    want = want[len(prompt) - 1:len(prompt) - 1 + len(got), :cfg.vocab_size]
    scale = want.std()
    np.testing.assert_allclose(got / scale, want / scale, atol=0.08)
    assert np.array_equal(np.argmax(got, -1)[:3], np.argmax(want, -1)[:3])


# ---------------------------------------------------------------------------
# The untied head, and the tied models' weights as they were
# ---------------------------------------------------------------------------
def test_an_untied_head_is_drawn_last_and_read_by_every_logits_site():
    cfg = get_smoke_config("deepseek_v2_lite")
    par = ParallelConfig(tp=1, dp=1)
    untied = M.init_model(jax.random.PRNGKey(5), cfg, par)
    tied = M.init_model(jax.random.PRNGKey(5),
                        dataclasses.replace(cfg, tie_embeddings=True), par)
    assert "head" in untied and "head" not in tied
    assert layers.head_table(untied) is untied["head"]
    assert layers.head_table(tied) is tied["embed"]
    assert M.param_specs(cfg, par, untied)["head"] == P("model", None)
    head = untied.pop("head")
    assert head.shape == untied["embed"].shape
    assert not np.array_equal(np.asarray(head), np.asarray(untied["embed"]))
    for a, b in zip(jax.tree.leaves(untied), jax.tree.leaves(tied)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


#: sha256 over every ``init_model`` leaf (path, shape, dtype, bytes) of the
#: benchmark's tied dense programs at small widths: the benchmark's
#: reference draws the same weights from the seed, so an untied model's
#: extra draw must leave these unchanged
TIED_DIGESTS = {
    ("minicpm_2b", 4, 1):
        "12fbcc908018b33577466290653dfea23c45f9509cf883b66dae353dcc57c3d6",
    ("minicpm_2b", 4, 4):
        "4a7ee57c59d81c123e612f5d062117f9ae0ae55d39f74d71dfac6d27fc595505",
    ("phi4_mini_38b", 2, 1):
        "ac0a90c0368c46e75be98fe48a49da136265b72c2019a277aeb0bc95d98f43a2",
    ("phi4_mini_38b", 2, 4):
        "0b438db3462549fd2023b7da6ba4334195987f379963a97b5588272a5598066f",
}


@pytest.mark.parametrize("arch,kv,tp", sorted(TIED_DIGESTS))
def test_tied_models_draw_the_weights_they_drew_before(arch, kv, tp):
    cfg = dataclasses.replace(get_config(arch), num_layers=2, d_model=128,
                              num_heads=4, num_kv_heads=kv, head_dim=32,
                              d_ff=256, vocab_size=512, tie_embeddings=True)
    params = M.init_model(jax.random.PRNGKey(7), cfg,
                          ParallelConfig(tp=tp, dp=1))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode() + str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == TIED_DIGESTS[(arch, kv, tp)]
