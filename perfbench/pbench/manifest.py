"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own:

* ``configs/<config>.json``   -- named by the manifest's ``file``
* ``traffic/<traffic>.json``  -- the mix's parameters
* ``metrics/<metric>.py``     -- a reader exposing ``read(run) -> float|None``
* ``references/<name>.py``    -- a configuration's plain reference
* ``counts/<name>.py``        -- a configuration's work counts, named by
  its file's ``work`` key (``pbench/work.py`` where it has none)

so a later cell, mix or metric is added with new files and manifest entries
only.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

#: the directory that holds this benchmark (``perfbench/``)
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the checkout root: BENCHMARK.json and the program live here
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str = BENCH_DIR
    readers: Dict[str, object] = field(default_factory=dict)


def load_manifest(root: str = ROOT) -> Dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise ManifestError(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def _load_json(path: str) -> Dict:
    if not os.path.exists(path):
        raise ManifestError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (metric readers and references are named by
    the metric or reference, which may hold a dot)."""
    if not os.path.exists(path):
        raise ManifestError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "pbench_ext_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(manifest: Dict, cell: str) -> (List[Dict], List[Dict]):
    """The end-to-end metrics a cell reports, and the per-layer metrics
    whose readers run in it: those that list the cell, or, without a
    ``workloads`` key, every cell that reports the metric they move."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in manifest["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def load_cell(name: str, root: str = ROOT,
              manifest: Optional[Dict] = None) -> Cell:
    manifest = manifest if manifest is not None else load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json "
                            f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in confs:
        raise ManifestError(f"workload {name!r} names unknown config "
                            f"{w['config']!r}")
    config = _load_json(os.path.join(root, confs[w["config"]]["file"]))
    bench_dir = os.path.join(root, os.path.dirname(os.path.dirname(
        confs[w["config"]]["file"])))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    e2e, per = metrics_for(manifest, name)
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per,
                bench_dir=bench_dir)
    for m in per:
        mod = load_module(os.path.join(bench_dir, "metrics",
                                       m["name"] + ".py"), m["name"])
        if not callable(getattr(mod, "read", None)):
            raise ManifestError(f"metric reader {m['name']} has no read()")
        cell.readers[m["name"]] = mod
    return cell


def load_reference(cell: Cell):
    ref = cell.config["reference"]
    return load_module(os.path.join(cell.bench_dir, "references",
                                    ref + ".py"), ref)


def load_counts(cell: Cell):
    """The module that counts the work of the cell's configuration: the
    one its file names under ``work``, else ``pbench/work.py``.  Either
    exposes ``Dims.of``, ``decode_work``, ``chunk_work`` and, for training,
    ``train_step_flops`` and ``seam_calls``."""
    name = cell.config.get("work")
    if name is None:
        from pbench import work
        return work
    return load_module(os.path.join(cell.bench_dir, "counts", name + ".py"),
                       "counts_" + name)
