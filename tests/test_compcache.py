"""utils/compcache: the persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed path in the
checkout — never under a temp name, a pid or a time.  Each case runs in
a fresh process: the cache directory is process-global jax config."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import json, os
import jax
jax.config.update("jax_platforms", "cpu")
from crdt_graph_tpu.utils import compcache
got = compcache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
print(json.dumps({"enabled": got,
                  "config": jax.config.jax_compilation_cache_dir,
                  "default": compcache.DEFAULT_DIR}))
"""


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_nothing_else_is_set(tmp_path):
    cache = tmp_path / "cc"
    got = _probe(str(cache))
    assert got["enabled"] == got["config"] == str(cache)
    assert any(cache.iterdir())          # the compile landed there


def test_default_is_one_fixed_path_in_the_checkout():
    first, second = _probe(None), _probe(None)
    assert first == second
    assert first["enabled"] == first["config"] == first["default"] == \
        os.path.join(REPO, ".jax_cache")
    assert os.path.isdir(first["default"])
