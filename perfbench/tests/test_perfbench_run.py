"""The command: no result without an accelerator, or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import pbench_tiny


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "minicpm_2b.chat",
         "--seed", str(2 ** 31 + 3), "--seconds", "2", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    out = _run(pbench_tiny.REPO)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
    assert "no TPU" in out.stderr


def test_with_only_the_benchmark_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(pbench_tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(pbench_tiny.BENCH, os.path.join(tmp_path, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert not _has_result(out.stdout)


@pytest.mark.parametrize("arg", [["--workload", "nope"]])
def test_unknown_workload_exits_nonzero(arg):
    out = _run(pbench_tiny.REPO, *arg)
    assert out.returncode != 0 and not _has_result(out.stdout)
