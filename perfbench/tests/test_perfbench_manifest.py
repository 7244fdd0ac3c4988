"""BENCHMARK.json keeps to its contract, and a configuration, a traffic
mix and a per-layer metric are added with files and manifest entries
only."""
import json
import os
import re
import shutil

import pytest

import pbench_tiny
from pbench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _manifest():
    with open(os.path.join(pbench_tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_keeps_to_the_contract():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= m["run_seconds"] <= 51
    names = set()
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perfbench/")
        assert os.path.exists(os.path.join(pbench_tiny.REPO, c["file"]))
        names.add(c["name"])
    used = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        used.add(w["config"])
    assert used == names
    for m_ in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(m_["name"]) and UNIT.match(m_["unit"])
        assert m_["better"] in ("lower", "higher")
    assert all(0.01 <= e["bound"] <= 0.25 for e in m["end_to_end"])
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for p in m["per_layer"]:
        assert p["moves"] in e2e
        for cell in p["workloads"]:
            # the cell reports the end-to-end metric this one moves
            assert cell in e2e[p["moves"]].get("workloads", [cell])
    assert "setup_s" in e2e


@pytest.mark.parametrize("cell", [w["name"] for w in _manifest()["workloads"]])
def test_every_cell_loads_with_its_readers(cell):
    c = manifest.load_cell(cell, pbench_tiny.REPO)
    assert c.end_to_end and c.per_layer
    assert {m["name"] for m in c.per_layer} == set(c.readers)
    assert any(m["name"] == "setup_s" for m in c.end_to_end)


def test_a_new_config_mix_and_metric_need_only_files(tmp_path):
    """Copy the benchmark, add a configuration, a mix and a metric as new
    files plus manifest entries, and load the new cell."""
    root = str(tmp_path)
    shutil.copytree(pbench_tiny.BENCH, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    m = _manifest()
    bench = os.path.join(root, "perfbench")
    with open(os.path.join(bench, "configs", "minicpm_2b.json")) as f:
        conf = json.load(f)
    conf["name"] = "other_model"
    with open(os.path.join(bench, "configs", "other_model.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(bench, "traffic", "chat.json")) as f:
        mix = json.load(f)
    mix["rate_rps"] = 0.25
    with open(os.path.join(bench, "traffic", "chat_slow.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "ticks.slow.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.calls))\n")
    m["configs"].append({"name": "other_model", "source": "x",
                         "file": "perfbench/configs/other_model.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "other_model.chat_slow",
                           "config": "other_model", "traffic": "chat_slow",
                           "chips": 1, "why": "test"})
    m["end_to_end"][0]["workloads"].append("other_model.chat_slow")
    m["per_layer"].append({"name": "ticks.slow", "unit": "count",
                           "better": "lower", "source": "host_clock",
                           "layer": "serving runtime",
                           "moves": m["end_to_end"][0]["name"],
                           "workloads": ["other_model.chat_slow"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    cell = manifest.load_cell("other_model.chat_slow", root)
    assert cell.config["name"] == "other_model"
    assert cell.traffic["rate_rps"] == 0.25
    assert list(cell.readers) == ["ticks.slow"]
    assert cell.readers["ticks.slow"].read(type("R", (), {"calls": [1]})) \
        == 1.0
    assert manifest.load_reference(cell).__name__.endswith("dense_llama")


def test_unknown_cell_is_an_error():
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no_such.cell", pbench_tiny.REPO)


def test_peaks_are_keyed_by_device_kind_and_an_unknown_device_is_an_error():
    import jax

    from pbench import harness
    peaks = harness.load_peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoChip):
        harness.find_devices(1, {"some other kind": {}})
    with pytest.raises(harness.NoChip):          # the CPU is no chip
        harness.find_devices(1, peaks)
    assert jax.devices()[0].platform == "cpu"
