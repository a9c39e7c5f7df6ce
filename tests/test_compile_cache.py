"""The persistent compile cache is where ``use_compile_cache`` puts it.

With ``JAX_COMPILATION_CACHE_DIR`` unset it lives at the fixed path
``<checkout>/.jax_cache``, and a second process finds what the first one
wrote.  With the variable set, that directory is used and no other.  Each
run is a fresh process, as each run of ``chip_smoke.py`` is; the checkout
path is redirected to a temporary directory.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CHECKOUT = sys.argv[1]
print("DIR", compile_cache.use_compile_cache())
# cache every compile, however small
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event) if event == "/jax/compilation_cache/cache_hits" else None)
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
print("HITS", len(hits))
"""


def _run(checkout, env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CHILD, str(checkout)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    out = dict(line.split(" ", 1) for line in r.stdout.splitlines() if " " in line)
    return out["DIR"], int(out["HITS"])


@pytest.mark.parametrize("from_env", [False, True])
def test_second_run_hits_the_cache(tmp_path, from_env):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    env_dir = tmp_path / "from_env" if from_env else None
    want = env_dir if from_env else checkout / ".jax_cache"

    d1, hits1 = _run(checkout, env_dir)
    d2, hits2 = _run(checkout, env_dir)
    assert d1 == d2 == str(want)
    assert os.listdir(want)
    assert hits1 == 0 and hits2 > 0
    if from_env:
        assert not (checkout / ".jax_cache").exists()
