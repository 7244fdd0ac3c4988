"""Where the launchers keep JAX's persistent compilation cache."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax
from repro.launch.cache import enable_compilation_cache
used = enable_compilation_cache()
print(used)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_dir_is_left_to_jax(tmp_path):
    used, configured = _probe(str(tmp_path))
    assert used == configured == str(tmp_path)


def test_default_is_fixed_path_in_checkout():
    used, configured = _probe(None)
    assert used == configured == os.path.join(REPO, ".jax_cache")
