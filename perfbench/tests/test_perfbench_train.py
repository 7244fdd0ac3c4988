"""The training cell's comparison: a sound run is correct; the float8
reference in the program's place and the program with each training fault
planted are not.  Whole runs of the tiny training cell on four CPU
devices, past the harness's look for a chip, in one subprocess."""
import json
import os
import subprocess
import sys

import pytest

import pbench_tiny

CASES = {"sound": {}, "control": {"control": True},
         "frozen": {"fault": "frozen"},
         "half_batch": {"fault": "half_batch"},
         "no_exchange": {"fault": "no_exchange"}}

SCRIPT = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import pbench_tiny
root = pbench_tiny.make(tempfile.mkdtemp())
for name, kw in json.loads(sys.argv[2]).items():
    res, lines = pbench_tiny.run(root, "minicpm_2b.train_sp_flux", **kw)
    info = [ln for ln in lines if ln.startswith("check: ")]
    print("RESULT " + json.dumps({"case": name, "res": res,
                                  "info": info[0][7:] if info else None}),
          flush=True)
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(pbench_tiny.REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, os.path.dirname(__file__),
         json.dumps(CASES)], env=env, capture_output=True, text=True,
        timeout=900)
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            got[r["case"]] = r
    assert set(got) == set(CASES), out.stderr[-4000:]
    return got


def test_sound_training_run_is_correct(results):
    r = results["sound"]["res"]
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]
    assert r["metrics"]["train_tok_s"]["value"] > 0


def test_control_in_the_programs_place_is_not_correct(results):
    r = results["control"]
    assert not r["res"]["correct"], r["res"]["checks"]
    program = json.loads(r["info"])["program"]
    held = r["res"]["checks"]
    assert all(program[k] <= held[k]["limit"] for k in held if k in program)


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "no_exchange"])
def test_planted_training_fault_is_not_correct(results, fault):
    r = results[fault]["res"]
    assert not r["correct"], r["checks"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_state_left_unchanged_reads_one(results):
    """A step that returns its state unchanged moves no leaf: the gap of
    the change's norm is the reference's whole norm."""
    c = results["frozen"]["res"]["checks"]
    assert c["change_norm_gap"]["value"] == pytest.approx(1.0)
