"""The tiny copy of the benchmark is built from files, one per
configuration and per mix, and a configuration enters the benchmark, the
tiny copy with it, as new files and manifest entries only."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import pbench_tiny
from pbench import manifest

#: the files the tiny copy of the manifest's cells holds, as the builder
#: wrote them when it still named every configuration and mix in tables of
#: its own (sha256)
BEFORE = {
    "perfbench/configs/minicpm_2b.json":
        "10c606a3f650dce4e530f424903f406b786fd9d87232034ec24706f90a49ceb3",
    "perfbench/configs/phi4_mini.json":
        "845ed2fa7fae64f5825913905a573c5a7e65ab3a35357f65af453b9bd0af8962",
    "perfbench/traffic/chat.json":
        "f6e326ab2e581fa6e63efdd50167a2d96dd505c1c969154a4e9a0fd635890541",
    "perfbench/traffic/code.json":
        "08219bfebfc6d16fab0ca5d21463e30379b8c3912e8228ba467b567452f51dff",
    "perfbench/traffic/train_sp_flux.json":
        "34c538731dcf06bb238d32a50afa9e4bdd7b3678699cf5a402f90daaab31dc50",
}


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def test_the_tiny_copy_is_the_one_the_tables_made(tmp_path):
    root = pbench_tiny.make(str(tmp_path))
    assert {p: _sha(os.path.join(root, p)) for p in BEFORE} == BEFORE
    with open(os.path.join(pbench_tiny.REPO, "BENCHMARK.json")) as f:
        want = pbench_tiny._with_training(json.load(f))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        assert json.load(f) == want


def _copy(dst):
    """The benchmark of this checkout, copied to ``dst``."""
    os.makedirs(dst)
    shutil.copy(os.path.join(pbench_tiny.REPO, "BENCHMARK.json"), dst)
    shutil.copytree(pbench_tiny.BENCH, os.path.join(dst, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(dst)


def _edit_manifest(src, edit):
    path = os.path.join(src, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    edit(m)
    with open(path, "w") as f:
        json.dump(m, f, indent=2)


def _write(src, rel, obj):
    path = os.path.join(src, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f, indent=2)


@pytest.mark.parametrize("missing", ["configs", "traffic"])
def test_a_name_without_a_tiny_file_is_named_in_the_error(tmp_path, missing):
    src = _copy(tmp_path / "src")
    os.remove(os.path.join(src, "perfbench", "tests", "tiny", missing,
                           {"configs": "phi4_mini", "traffic": "code"}[missing]
                           + ".json"))
    with pytest.raises(manifest.ManifestError) as e:
        pbench_tiny.make(str(tmp_path / "tiny"), src=src)
    assert os.path.join("tests", "tiny", missing) in str(e.value)


#: a throwaway dense configuration, its mix and a four-chip serving cell
CONF = {"name": "dense4",
        "source": "a dense decoder with a KV head for each query head",
        "model": {"num_hidden_layers": 4, "hidden_size": 1024,
                  "num_attention_heads": 8, "num_key_value_heads": 8,
                  "head_dim": 128, "intermediate_size": 2816,
                  "vocab_size": 32000, "rms_norm_eps": 1e-05,
                  "rope_theta": 10000.0, "tie_word_embeddings": True},
        "program": {"arch": "minicpm_2b", "overrides": {
            "num_layers": 4, "d_model": 1024, "num_heads": 8,
            "num_kv_heads": 8, "head_dim": 128, "d_ff": 2816,
            "vocab_size": 32000, "tie_embeddings": True}},
        "reference": "dense_llama", "check": {"logit_gap": 0.25},
        "server": {"max_batch": 8, "max_seq": 1024, "block_size": 16,
                   "prefill_chunk": 256, "max_new_tokens": 32,
                   "eos_token": -1},
        "check_requests": 4}
TINY_CONF = {"why": "one KV head on each of four chips",
             "model": {"num_hidden_layers": 2, "hidden_size": 128,
                       "num_attention_heads": 4, "num_key_value_heads": 4,
                       "head_dim": 32, "intermediate_size": 256,
                       "vocab_size": 512, "rms_norm_eps": 1e-05,
                       "rope_theta": 10000.0, "tie_word_embeddings": True},
             "program": {"arch": "minicpm_2b", "overrides": {
                 "num_layers": 2, "d_model": 128, "num_heads": 4,
                 "num_kv_heads": 4, "head_dim": 32, "d_ff": 256,
                 "vocab_size": 512, "tie_embeddings": True}},
             "server": {"max_batch": 4, "max_seq": 128, "block_size": 8,
                        "prefill_chunk": 16, "eos_token": -1,
                        "max_new_tokens": 12},
             "check_requests": 3}
MIX = {"loop": "open", "rate_rps": 0.5, "lead_s": 10, "drain_cap_s": 60,
       "prompt": {"dist": "lognormal", "median": 200, "sigma": 0.5,
                  "min": 32, "max": 600}}
TINY_MIX = {"loop": "open", "rate_rps": 3, "lead_s": 0.5, "drain_cap_s": 30,
            "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 48}}
CELL = "dense4.burst4"
READER = ("from pbench import layers\n\n\ndef read(run):\n"
          "    return layers.collective_pct(run, exposed=True)\n")


def _add_cell(m):
    m["configs"].append({"name": "dense4", "source": CONF["source"],
                         "file": "perfbench/configs/dense4.json",
                         "reduced": [], "why": "throwaway"})
    m["workloads"].append({"name": CELL, "config": "dense4",
                           "traffic": "burst4", "chips": 4,
                           "why": "tensor-parallel serving on four chips"})
    tail = next(e for e in m["end_to_end"] if e["name"] == "tpot_p90_ms")
    tail["workloads"].append(CELL)
    m["per_layer"].append({"name": "collective_exposed_pct.burst4",
                           "unit": "%", "better": "lower",
                           "source": "device_trace",
                           "layer": "compiled programs",
                           "moves": "tpot_p90_ms", "workloads": [CELL]})


SCRIPT = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import pbench_tiny
root = pbench_tiny.make(tempfile.mkdtemp())
res, lines = pbench_tiny.run(root, sys.argv[2])
print("RESULT " + json.dumps(res), flush=True)
print("\n".join(lines), file=sys.stderr)
"""


def test_a_four_chip_serving_cell_enters_as_new_files_only(tmp_path):
    """A configuration, a mix, a reader and a four-chip serving cell, added
    to a copy of the benchmark as new files and manifest entries, run
    correct in the tiny copy on four CPU devices; no file the benchmark
    had is edited."""
    src = _copy(tmp_path / "src")
    had = {os.path.relpath(os.path.join(d, f), src)
           for d, _, fs in os.walk(os.path.join(src, "perfbench"))
           for f in fs}
    _write(src, "perfbench/configs/dense4.json", CONF)
    _write(src, "perfbench/traffic/burst4.json", MIX)
    _write(src, "perfbench/tests/tiny/configs/dense4.json", TINY_CONF)
    _write(src, "perfbench/tests/tiny/traffic/burst4.json", TINY_MIX)
    _write(src, "perfbench/metrics/collective_exposed_pct.burst4.py", READER)
    _edit_manifest(src, _add_cell)
    for rel in had:
        assert _sha(os.path.join(src, rel)) == _sha(
            os.path.join(pbench_tiny.REPO, rel)), rel
    cell = manifest.load_cell(CELL, src)
    assert cell.chips == 4 and list(cell.readers) == [
        "collective_exposed_pct.burst4"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(pbench_tiny.REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT,
         os.path.join(src, "perfbench", "tests"), CELL],
        env=env, capture_output=True, text=True, timeout=900)
    got = [json.loads(ln[len("RESULT "):]) for ln in out.stdout.splitlines()
           if ln.startswith("RESULT ")]
    assert got, out.stderr[-4000:]
    res = got[0]
    assert res["correct"], (res["checks"], out.stderr[-4000:])
    assert res["device"]["count"] == 4
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["tpot_p90_ms"]["value"] > 0
