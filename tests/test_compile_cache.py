"""The launchers' persistent compilation cache: JAX's own
``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed directory in the
checkout.  Each case runs in a child process, so this process's JAX config
(cache off, see conftest.py) is left alone."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_updates, action):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_updates)
    proc = subprocess.run([sys.executable, "-c", _PROBE, action], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_cache_dir_is_used_as_set(tmp_path):
    cache = tmp_path / "jaxcache"
    got = _probe({"JAX_COMPILATION_CACHE_DIR": str(cache),
                  "JAX_ENABLE_COMPILATION_CACHE": "true"}, "compile")
    assert got["path"] == str(cache) and got["config"] == str(cache)
    assert cache.is_dir() and any(cache.iterdir())   # the run wrote there


def test_unset_env_uses_the_fixed_checkout_dir():
    # no compile: the probe only reports where the cache would go, so the
    # checkout gains no files
    got = _probe({"JAX_ENABLE_COMPILATION_CACHE": "true"}, "report")
    assert got["path"] == os.path.join(ROOT, ".jax_cache")
    assert got["config"] == got["path"]


def test_cache_off_sets_nothing():
    got = _probe({"JAX_ENABLE_COMPILATION_CACHE": "false"}, "report")
    assert got["path"] is None and got["config"] is None
