"""The DeepSeek-V2-Lite cell in the tiny copy: the served tokens agree with
the plain reference (``references/deepseek_mla_moe.py``) at one and at
four CPU devices, and the float8 control in the program's place does not.
Its work counts stay under the roofline whichever attention form a call
takes."""
import json
import os
import subprocess
import sys

import pytest

import pbench_tiny
from pbench import manifest

CELL = "deepseek_v2_lite.docqa"
#: the window of a tiny run: a request of the tiny mix takes about a second
#: on four CPU devices alone, and the check needs finished requests even
#: when the other tests share the cores
SECONDS = 4.0

SCRIPT = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import pbench_tiny
root = pbench_tiny.make(tempfile.mkdtemp())
for control in (False, True):
    res, lines = pbench_tiny.run(root, sys.argv[2],
                                 seconds=float(sys.argv[3]), control=control)
    info = [ln for ln in lines if ln.startswith("check: ")]
    print("RESULT " + json.dumps([res, info]), flush=True)
"""


def _check_pair(results):
    (sound, _), (control, info) = results
    assert sound["correct"], sound["checks"]
    gap = sound["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert not control["correct"], control["checks"]
    gap = control["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    program = json.loads(info[0][len("check: "):])["program_logit_gap"]
    assert program <= gap["limit"]


def test_one_device_sound_run_is_correct_and_the_control_is_not(tmp_path):
    """The cell's tiny copy on the one CPU device a test process has."""
    root = pbench_tiny.make(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    for w in m["workloads"]:
        if w["name"] == CELL:
            w["chips"] = 1
    with open(path, "w") as f:
        json.dump(m, f)
    results = []
    for control in (False, True):
        res, lines = pbench_tiny.run(root, CELL, seconds=SECONDS,
                                     control=control)
        results.append((res, [ln for ln in lines
                              if ln.startswith("check: ")]))
    _check_pair(results)


def test_four_device_sound_run_is_correct_and_the_control_is_not():
    """tp=4 on four CPU devices, as the cell runs on a four-chip host:
    heads, FFN columns and the vocabulary split four ways, four experts a
    device, combined by the psum."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(pbench_tiny.REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT,
         os.path.join(pbench_tiny.BENCH, "tests"), CELL, str(SECONDS)],
        env=env, capture_output=True, text=True, timeout=900)
    got = [json.loads(ln[len("RESULT "):]) for ln in out.stdout.splitlines()
           if ln.startswith("RESULT ")]
    assert len(got) == 2, out.stderr[-4000:]
    assert got[0][0]["device"]["count"] == 4
    _check_pair(got)


def test_published_keys_agree_at_the_top_level_and_under_model():
    """The file states the published config.json twice: at its top level,
    as the source gives it, and under ``model``, which the harness reads.
    The two copies hold the same keys and values."""
    conf = manifest.load_cell(CELL, pbench_tiny.REPO).config
    model = conf["model"]
    assert {k: conf.get(k) for k in model} == model
    assert model["q_lora_rank"] is None
    assert model["first_k_dense_replace"] == 1


def _counts():
    cell = manifest.load_cell(CELL, pbench_tiny.REPO)
    counts = manifest.load_counts(cell)
    return counts, counts.Dims.of(cell.config["model"])


def test_counts_match_the_published_sizes():
    counts, d = _counts()
    # 15.7B parameters: layers, experts, embedding and the untied head
    total = (d.layers * (d.attn_proj_params + d.up_params)
             + d.dense_layers * 3 * d.d_model * d.d_ff
             + d.expert_layers * (d.router_params
                                  + (d.shared + d.experts) * d.expert_params)
             + 2 * d.vocab * d.d_model)
    assert 15.6e9 < total < 15.8e9
    # one decode row touches top-k of the 64 experts; a 256-row chunk
    # nearly all of them
    assert counts._experts_touched(d, 1) == pytest.approx(d.top_k)
    assert counts._experts_touched(d, 256) > 63.9


@pytest.mark.parametrize("off,length", [(0, 256), (4096, 256), (15616, 256),
                                        (100, 37)])
def test_attention_takes_the_cheaper_form(off, length):
    """The absorbed form for short contexts and decode, the expanded one
    for long chunks; either way no more than each form alone."""
    counts, d = _counts()
    ctx = [off + j + 1 for j in range(length)]
    h, r = d.heads, d.kv_rank
    absorbed = len(ctx) * 2 * h * r * (d.nope + d.v_dim) \
        + 2 * h * (2 * r + d.rope) * sum(ctx)
    expanded = 2 * d.up_params * max(ctx) \
        + 2 * h * (d.nope + d.rope + d.v_dim) * sum(ctx)
    assert d.attn_flops(ctx) == min(absorbed, expanded)
    # a decode query against a long cache takes the absorbed form
    assert d.attn_flops([off + 4096]) == 2 * h * r * (d.nope + d.v_dim) \
        + 2 * h * (2 * r + d.rope) * (off + 4096)
    w = counts.chunk_work(d, off, length, final=False)
    assert w.flops > length * 2 * d.per_token_params
