"""A tiny copy of the benchmark for CPU tests: the real manifest's cells
pointed at a two-layer configuration, with short traffic mixes."""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
              "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
              "tie_word_embeddings": True}
TINY_PROGRAM = {"arch": "minicpm_2b", "overrides": {
    "num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 32, "d_ff": 256, "vocab_size": 512}}
#: per configuration: MiniCPM keeps a KV head per query head, so every
#: chip of four holds whole heads in training
KV_HEADS = {"minicpm_2b": 4, "phi4_mini": 2}
SERVER = {"max_batch": 4, "max_seq": 128, "block_size": 8,
          "prefill_chunk": 16, "eos_token": -1}
SERVERS = {"minicpm_2b": (dict(SERVER, max_new_tokens=32), 4),
           "phi4_mini": (dict(SERVER, max_new_tokens=16), 8)}
MIXES = {
    "chat": {"loop": "open", "rate_rps": 4, "lead_s": 0.5, "drain_cap_s": 30,
             "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                        "min": 8, "max": 60}},
    "code": {"loop": "closed", "clients": 6, "lead_s": 0.5, "requests": 400,
             "block": 16,
             "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.4,
                        "min": 20, "max": 90}},
    # the fused kernels' DMAs do not run interpreted on a mesh of more than
    # one axis, so on the CPU the training mix takes the decomposed seams
    "train_sp_flux": {"loop": "train", "global_batch": 4, "seq_len": 32,
                      "overlap_mode": "decomposed", "checked_steps": 3},
}


#: the four-chip training cell and its metrics, for the tiny copy while the
#: manifest does not hold them yet (its measured bound is still to come)
TRAIN_CELL = {"name": "minicpm_2b.train_sp_flux", "config": "minicpm_2b",
              "traffic": "train_sp_flux", "chips": 4,
              "why": "tp=4 sequence-parallel training"}
TRAIN_E2E = {"name": "train_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.25, "source": "host_clock",
             "workloads": [TRAIN_CELL["name"]]}
TRAIN_LAYERS = [("ag_gemm_roofline_pct.train", "%", "higher", "kernels"),
                ("gemm_rs_roofline_pct.train", "%", "higher", "kernels"),
                ("mfu.train", "%", "higher", "whole step"),
                ("idle_pct.train", "%", "lower", "device")]


def _with_training(manifest: dict) -> dict:
    if any(w["name"] == TRAIN_CELL["name"] for w in manifest["workloads"]):
        return manifest
    manifest["workloads"].append(TRAIN_CELL)
    manifest["end_to_end"].append(TRAIN_E2E)
    manifest["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": "device_trace",
         "layer": layer, "moves": "train_tok_s",
         "workloads": [TRAIN_CELL["name"]]}
        for n, u, b, layer in TRAIN_LAYERS]
    return manifest


def make(root: str, limit: float = None) -> str:
    """Write the tiny benchmark under ``root``; returns ``root``.  Every
    configuration keeps the cell's own limit unless ``limit`` is given."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = _with_training(json.load(f))
    bench = os.path.join(root, "perfbench")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("metrics", "references"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub),
                        dirs_exist_ok=True)
    for conf in manifest["configs"]:
        with open(os.path.join(REPO, conf["file"])) as f:
            real = json.load(f)
        kv = KV_HEADS[conf["name"]]
        program = dict(TINY_PROGRAM, overrides=dict(
            TINY_PROGRAM["overrides"], num_kv_heads=kv))
        server, check_requests = SERVERS[conf["name"]]
        tiny = {"name": conf["name"],
                "model": dict(TINY_MODEL, num_key_value_heads=kv),
                "program": program, "reference": real["reference"],
                "check": dict(real["check"]), "server": server,
                "check_requests": check_requests}
        if "train" in real:
            tiny["train"] = real["train"]
        if limit is not None:
            tiny["check"]["logit_gap"] = limit
        with open(os.path.join(root, conf["file"]), "w") as f:
            json.dump(tiny, f)
    for w in manifest["workloads"]:
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json"),
                  "w") as f:
            json.dump(MIXES[w["traffic"]], f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run(root: str, cell: str, seed: int = 2 ** 31 + 5, seconds: float = 2.0,
        control: bool = False, fault: str = None):
    """Drive a whole run of ``cell`` on the CPU, past the harness's look
    for a chip; returns (result, log lines)."""
    import jax

    from pbench import harness, manifest
    lines = []
    c = manifest.load_cell(cell, root)
    res = harness.run_cell(c, seed, seconds, False, jax.devices()[:c.chips],
                           PEAK, control=control, log=lines.append,
                           fault=fault)
    return res, lines
