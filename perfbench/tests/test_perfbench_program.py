"""The program's check at set-up: the ``ModelConfig`` fields a
configuration file's published keys state, compared with the program's."""
import dataclasses
import json
import os

import pytest

import pbench_tiny
from pbench import serving

#: what the check compared for every file before it read the expert and
#: latent-attention keys
TODAY = {
    "minicpm_2b": {"num_layers": 40, "d_model": 2304, "num_heads": 36,
                   "num_kv_heads": 36, "d_ff": 5760, "vocab_size": 122753,
                   "resolved_head_dim": 64, "norm_eps": 1e-05,
                   "rope_theta": 10000.0, "tie_embeddings": True,
                   "rope_style": "rope", "pattern": (("attn", "ffn"),),
                   "moe": None, "qkv_bias": False},
    "phi4_mini": {"num_layers": 32, "d_model": 3072, "num_heads": 24,
                  "num_kv_heads": 8, "d_ff": 8192, "vocab_size": 200064,
                  "resolved_head_dim": 128, "norm_eps": 1e-05,
                  "rope_theta": 10000.0, "tie_embeddings": True,
                  "rope_style": "rope", "pattern": (("attn", "ffn"),),
                  "moe": None, "qkv_bias": False},
}


def _conf(name):
    with open(os.path.join(pbench_tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _cell(conf):
    return type("Cell", (), {"config": conf})


@pytest.mark.parametrize("name", sorted(TODAY))
def test_a_dense_file_compares_what_it_compared_before(name):
    conf = _conf(name)
    assert serving.expected_fields(conf) == TODAY[name]
    cfg = serving.model_config(_cell(conf))
    assert cfg.moe is None and cfg.pattern == (("attn", "ffn"),)


def _deepseek_smoke():
    """A file that states the program's small DeepSeek-V3 (latent
    attention, routed and shared experts, a leading dense layer) by its
    published keys, and the program's config it should give."""
    from repro.configs.base import get_config, get_smoke_config
    small = get_smoke_config("deepseek_v3_671b")
    full = get_config("deepseek_v3_671b")
    model = {"num_hidden_layers": small.num_layers,
             "hidden_size": small.d_model,
             "num_attention_heads": small.num_heads,
             "num_key_value_heads": small.num_kv_heads,
             "intermediate_size": small.d_ff,
             "vocab_size": small.vocab_size, "rms_norm_eps": small.norm_eps,
             "rope_theta": small.rope_theta,
             "tie_word_embeddings": small.tie_embeddings,
             "attention_bias": False,
             "n_routed_experts": small.moe.num_experts,
             "num_experts_per_tok": small.moe.top_k,
             "moe_intermediate_size": small.moe.expert_ffn,
             "n_shared_experts": small.moe.num_shared_experts,
             "first_k_dense_replace": small.leading_dense_layers,
             **dataclasses.asdict(small.mla)}
    overrides = {}
    for f in dataclasses.fields(small):
        mine, theirs = getattr(small, f.name), getattr(full, f.name)
        if mine != theirs:
            overrides[f.name] = (dataclasses.asdict(mine)
                                 if dataclasses.is_dataclass(mine) else mine)
    conf = {"name": "deepseek_small", "model": model,
            "program": {"arch": "deepseek_v3_671b",
                        "overrides": json.loads(json.dumps(overrides))}}
    return conf, small


def test_expert_and_latent_keys_are_compared():
    conf, small = _deepseek_smoke()
    want = serving.expected_fields(conf)
    assert want["pattern"] == (("mla", "moe"),)
    assert want["leading_dense_layers"] == small.leading_dense_layers == 1
    assert {k for k in want if k.startswith(("moe.", "mla."))} == {
        "moe.num_experts", "moe.top_k", "moe.expert_ffn",
        "moe.num_shared_experts", "moe.shared_ffn", "mla.q_lora_rank",
        "mla.kv_lora_rank", "mla.qk_nope_head_dim", "mla.qk_rope_head_dim",
        "mla.v_head_dim"}
    # latent attention sizes its heads by its own keys
    assert "resolved_head_dim" not in want and "moe" not in want
    assert serving.model_config(_cell(conf)) == small


@pytest.mark.parametrize("key,field", [
    ("n_routed_experts", "moe.num_experts"),
    ("num_experts_per_tok", "moe.top_k"),
    ("moe_intermediate_size", "moe.expert_ffn"),
    ("n_shared_experts", "moe.num_shared_experts"),
    ("first_k_dense_replace", "leading_dense_layers"),
    ("kv_lora_rank", "mla.kv_lora_rank"),
    ("attention_bias", "qkv_bias")])
def test_a_program_that_differs_in_one_key_is_refused(key, field):
    conf, _ = _deepseek_smoke()
    conf["model"][key] = conf["model"][key] + 1
    with pytest.raises(ValueError, match=field.replace(".", r"\.")):
        serving.model_config(_cell(conf))


def test_a_dense_file_refuses_a_program_with_experts():
    conf = _conf("phi4_mini")
    conf["program"] = _deepseek_smoke()[0]["program"]
    with pytest.raises(ValueError, match="pattern"):
        serving.model_config(_cell(conf))


def test_a_reduced_key_is_not_compared():
    """The chip's share of the experts: the file states how many are held
    here and lists the key under ``reduced``."""
    conf, small = _deepseek_smoke()
    conf["model"]["n_routed_experts"] = 64
    with pytest.raises(ValueError):
        serving.model_config(_cell(conf))
    conf["reduced"] = ["n_routed_experts"]
    assert "moe.num_experts" not in serving.expected_fields(conf)
    assert serving.model_config(_cell(conf)) == small


def test_a_file_without_a_key_the_check_reads_is_named():
    conf = _conf("minicpm_2b")
    del conf["model"]["rope_theta"]
    with pytest.raises(ValueError, match="rope_theta"):
        serving.expected_fields(conf)
