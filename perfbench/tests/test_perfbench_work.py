"""The benchmark's own work counts, from unpadded shapes."""
import dataclasses
import json
import os
import shutil

import pytest

import pbench_tiny
from pbench import work


def _model(name):
    with open(os.path.join(pbench_tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["minicpm_2b", "phi4_mini"])
def test_params_match_the_programs_count_without_padding(name):
    """Every parameter the program holds, less the zero rows that pad its
    vocabulary to a multiple of 128."""
    import jax  # noqa: F401

    from repro.configs.base import get_config
    from repro.models.model import count_params_analytic
    from repro.parallel.sharding import pad_vocab
    conf = _model(name)
    cfg = dataclasses.replace(get_config(conf["program"]["arch"]),
                              **conf["program"]["overrides"])
    d = work.Dims.of(conf["model"])
    pad_rows = pad_vocab(d.vocab, 1) - d.vocab
    assert d.params == count_params_analytic(cfg) - pad_rows * d.d_model


def test_published_sizes():
    assert work.Dims.of(_model("minicpm_2b")["model"]).params == 2724880896
    phi = work.Dims.of(_model("phi4_mini")["model"])
    assert phi.params == 3836021760
    # 32 layers x 2 (K, V) x 8 heads x 128 x bf16
    assert phi.kv_bytes_per_token == 131072


def test_decode_bytes_count_true_lengths_not_the_view():
    d = work.Dims.of(_model("minicpm_2b")["model"])
    w = work.decode_work(d, [0, 99])
    weights = d.layer_weight_bytes + d.head_weight_bytes
    assert w.bytes == weights + (1 + 100) * d.kv_bytes_per_token
    # adding a slot adds its K/V and its tokens' FLOPs, not another weight
    # read
    w2 = work.decode_work(d, [0, 99, 9])
    assert w2.bytes - w.bytes == 10 * d.kv_bytes_per_token
    assert w2.flops - w.flops == (d.dense_flops_per_token + d.head_flops
                                  + d.attn_flops(10))


def test_chunk_counts_its_true_length_and_the_head_once():
    d = work.Dims.of(_model("phi4_mini")["model"])
    mid = work.chunk_work(d, off=256, length=256, final=False)
    last = work.chunk_work(d, off=512, length=7, final=True)
    assert mid.flops == 256 * d.dense_flops_per_token + sum(
        d.attn_flops(256 + j + 1) for j in range(256))
    assert last.flops == 7 * d.dense_flops_per_token + d.head_flops + sum(
        d.attn_flops(512 + j + 1) for j in range(7))
    assert last.bytes == (d.layer_weight_bytes + d.head_weight_bytes
                          + 519 * d.kv_bytes_per_token)


def test_least_time_takes_the_larger_roof():
    w = work.Work(flops=2e12, bytes=1e9)
    assert w.least_s(1e12, 1e9) == 2.0
    assert w.least_s(1e13, 1e8) == 10.0


def test_training_flops_are_six_per_parameter_plus_attention():
    """Forward and backward, no recompute: six FLOPs per matmul parameter
    and token (every parameter but the norm scales, the tied embedding as
    the LM head), and three times the causal attention."""
    d = work.Dims.of(_model("minicpm_2b")["model"])
    b, s = 8, 256
    matmul_params = d.params - (2 * d.layers + 1) * d.d_model
    attn = 3 * b * sum(d.attn_flops(j + 1) for j in range(s))
    assert work.train_step_flops(d, b, s) == pytest.approx(
        6 * b * s * matmul_params + attn, rel=1e-12)


def test_seam_kernels_count_unpadded_shards():
    """At tp=4 MiniCPM's FFN shard is 1440 columns (the program pads it to
    1536) and the seams' FLOPs on the four chips are the forward's and the
    input gradients' matmuls: twice the forward's."""
    d = work.Dims.of(_model("minicpm_2b")["model"])
    b, s, tp = 8, 256, 4
    calls = work.seam_calls(d, b, s, tp)
    m = b * s
    assert calls["ag_gemm"][1].flops == 2 * m * d.d_model * 2 * 1440
    assert calls["gemm_rs"][1].flops == 2 * m * 1440 * d.d_model
    assert len(calls["ag_gemm"]) == len(calls["gemm_rs"]) == 4 * d.layers + 1
    total = tp * sum(w.flops for k in calls for w in calls[k])
    vocab_padded = tp * -(-d.vocab // tp)
    fwd = m * (d.dense_flops_per_token + 2 * vocab_padded * d.d_model)
    assert total == pytest.approx(2 * fwd, rel=1e-12)
    # an AllGather-GEMM touches the gathered rows, its shard and its
    # output once; a GEMM-ReduceScatter writes only its share of the rows
    ag = work.ag_gemm_work(m, 2304, 1728)
    assert ag.bytes == 2 * (m * 2304 + 2304 * 1728 + m * 1728)
    rs = work.gemm_rs_work(m, 576, 2304, tp)
    assert rs.bytes == 2 * (m * 576 + 576 * 2304 + m // tp * 2304)


def test_an_untied_head_is_counted_once_more_and_costs_a_step_the_same():
    tied = _model("phi4_mini")["model"]
    d, u = work.Dims.of(tied), work.Dims.of(dict(tied,
                                                 tie_word_embeddings=False))
    assert u.params - d.params == d.vocab * d.d_model
    assert u.head_weight_bytes == d.head_weight_bytes
    assert work.decode_work(u, [5, 70]) == work.decode_work(d, [5, 70])
    assert work.chunk_work(u, 0, 256, True) == work.chunk_work(d, 0, 256, True)


def test_a_configuration_without_counts_of_its_own_takes_pbench_work():
    from pbench import manifest
    cell = manifest.load_cell("minicpm_2b.chat", pbench_tiny.REPO)
    assert manifest.load_counts(cell) is work


COUNTS = '''"""Counts that mark what they counted."""
from pbench.work import Dims, Work, chunk_work  # noqa: F401


def decode_work(dims, positions):
    return Work(flops=1.0, bytes=float(len(positions)))
'''


def test_a_named_counts_module_is_loaded_and_counts_the_steps(tmp_path):
    """A configuration file names its counts (``"work": "<name>"``); the
    module under ``counts/`` is found by that name, and the readers'
    ``RunData`` counts each dispatch with it."""
    from pbench import layers, manifest, serving
    bench = tmp_path / "perfbench"
    shutil.copytree(pbench_tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench / "counts").mkdir(exist_ok=True)
    (bench / "counts" / "marked.py").write_text(COUNTS)
    conf = _model("minicpm_2b")
    conf["work"] = "marked"
    (bench / "configs" / "minicpm_2b.json").write_text(json.dumps(conf))
    shutil.copy(os.path.join(pbench_tiny.REPO, "BENCHMARK.json"), tmp_path)
    cell = manifest.load_cell("minicpm_2b.chat", str(tmp_path))
    counts = manifest.load_counts(cell)
    assert counts is not work and counts.__doc__.startswith("Counts that")
    dims = counts.Dims.of(conf["model"])
    run = layers.RunData(cell, dims, {}, 1, [], None, None, counts)
    call = serving.Call("decode", 0.0, 1.0, (3, 9, 11), 3)
    assert run.work(call) == work.Work(1.0, 3.0)
    # without the key the same call is counted by pbench/work.py
    plain = layers.RunData(cell, dims, {}, 1, [], None, None)
    assert plain.work(call) == work.decode_work(dims, (3, 9, 11))
