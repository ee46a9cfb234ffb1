"""``repro.compile_cache.use_compile_cache``: a fixed cache directory,
placed from outside by ``JAX_COMPILATION_CACHE_DIR`` when it is set."""

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
REPO = os.path.dirname(SRC)

# Compiles one small program with the cache on and reports where the
# cache went and what it holds.
SCRIPT = r"""
import json, os
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
path = use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1).lower(jnp.ones(7)).compile()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir,
                  "entries": sorted(os.listdir(path))}))
"""


def _run(env_dir):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_places_the_cache(tmp_path):
    where = str(tmp_path / "cache")
    res = _run(where)
    assert res["path"] == res["config"] == where
    assert res["entries"], "nothing was written to the cache"


def test_default_cache_is_fixed_under_the_repo():
    res = _run(None)
    assert res["path"] == res["config"] == os.path.join(REPO, ".jax_cache")
    assert res["entries"], "nothing was written to the cache"
