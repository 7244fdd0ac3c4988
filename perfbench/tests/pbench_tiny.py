"""A tiny copy of the benchmark for CPU tests: the real manifest's cells,
each configuration and traffic mix replaced by its tiny copy, a file of
its own under ``tiny/configs/<config>.json`` (two layers at small widths,
the program it runs, its server and how many requests the check samples)
and ``tiny/traffic/<mix>.json`` (short mixes).  A configuration or mix
enters the tiny copy with its file; nothing here names one."""
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

from pbench import manifest  # noqa: E402

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


def _with_training(m: dict) -> dict:
    if any(w["name"] == TRAIN_CELL["name"] for w in m["workloads"]):
        return m
    m["workloads"].append(TRAIN_CELL)
    m["end_to_end"].append(TRAIN_E2E)
    m["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": "device_trace",
         "layer": layer, "moves": "train_tok_s",
         "workloads": [TRAIN_CELL["name"]]}
        for n, u, b, layer in TRAIN_LAYERS]
    return m


def _tiny(src: str, kind: str, name: str) -> dict:
    """The tiny copy's file of configuration or mix ``name``; its ``why``
    is for the reader and is not written."""
    path = os.path.join(src, "perfbench", "tests", "tiny", kind,
                        name + ".json")
    if not os.path.exists(path):
        raise manifest.ManifestError(f"no tiny copy of {name!r}: missing "
                                     f"file {path}")
    with open(path) as f:
        got = json.load(f)
    got.pop("why", None)
    return got


def make(root: str, limit: float = None, src: str = REPO) -> str:
    """Write the tiny benchmark of the checkout ``src`` under ``root``;
    returns ``root``.  Each configuration and mix of the manifest is taken
    from its file under ``perfbench/tests/tiny/``; a configuration keeps
    its real file's reference, limits, training recipe and work counts.
    Every configuration keeps the cell's own limit unless ``limit`` is
    given."""
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        bench_manifest = _with_training(json.load(f))
    bench = os.path.join(root, "perfbench")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("metrics", "references", "counts"):
        if os.path.isdir(os.path.join(src, "perfbench", sub)):
            shutil.copytree(os.path.join(src, "perfbench", sub),
                            os.path.join(bench, sub), dirs_exist_ok=True)
    for conf in bench_manifest["configs"]:
        with open(os.path.join(src, conf["file"])) as f:
            real = json.load(f)
        t = _tiny(src, "configs", conf["name"])
        tiny = {"name": conf["name"], "model": t["model"],
                "program": t["program"], "reference": real["reference"],
                "check": dict(real["check"]), "server": t["server"],
                "check_requests": t["check_requests"]}
        for key in ("train", "work"):
            if key in real:
                tiny[key] = real[key]
        if limit is not None:
            tiny["check"]["logit_gap"] = limit
        with open(os.path.join(root, conf["file"]), "w") as f:
            json.dump(tiny, f)
    for w in bench_manifest["workloads"]:
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json"),
                  "w") as f:
            json.dump(_tiny(src, "traffic", w["traffic"]), f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench_manifest, f)
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
