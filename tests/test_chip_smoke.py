"""``chip_smoke.py`` refuses to run anywhere but on a TPU."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cwd, script, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_fails_without_tpu():
    out = _run(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_fails_alone_in_a_directory(tmp_path):
    """Copied out of the checkout, the script finds no program to run."""
    script = tmp_path / "chip_smoke.py"
    script.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    out = _run(str(tmp_path), str(script))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
