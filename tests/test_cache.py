"""Where the persistent compilation cache lands (`repro.utils.cache`).

Each case runs in a fresh interpreter: jax initializes its cache once
per process, so only a new process shows where a compile is written.
"""
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT = os.path.join(REPO, "experiments", ".jax_cache")

PROBE = """
import jax, jax.numpy as jnp
from repro.utils.cache import enable_compilation_cache
path = enable_compilation_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(cwd, cache_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()[-2:]


def test_cache_dir_from_environment(tmp_path):
    """The variable places the cache; the code sets no directory of its
    own, so jax's setting is the variable's value unchanged."""
    target = str(tmp_path / "xla-cache")
    returned, configured = _probe(tmp_path, cache_env=target)
    assert returned == configured == target
    assert os.listdir(target)


def test_cache_dir_default_is_absolute_in_checkout(tmp_path):
    """Unset, every cwd gets the same absolute in-checkout directory."""
    (tmp_path / "elsewhere").mkdir()
    seen = {tuple(_probe(cwd)) for cwd in (tmp_path / "elsewhere", REPO)}
    assert seen == {(DEFAULT, DEFAULT)}
    assert os.listdir(DEFAULT)
    assert not (tmp_path / "elsewhere" / "experiments").exists()
