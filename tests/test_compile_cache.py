"""Where the persistent XLA compile cache lives (presto_tpu/config.py).

JAX_COMPILATION_CACHE_DIR wins and the program sets no directory itself;
otherwise every process started from one checkout uses the same fixed
in-checkout path (the path is part of the cache key, so a directory built
from the host, the user or the time would never hit)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = (
    "import jax\n"
    "calls = []\n"
    "update = jax.config.update\n"
    "jax.config.update = lambda k, v: (calls.append(k), update(k, v))[1]\n"
    "import presto_tpu\n"
    "from jax._src import xla_bridge\n"
    "assert not xla_bridge._backends, 'import initialised a backend'\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print('jax_compilation_cache_dir' in calls)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
)
THRESHOLD = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


def probe(env, cwd=ROOT):
    r = subprocess.run([sys.executable, "-c", PROBE], cwd=cwd, env=dict(
        env, PYTHONPATH=ROOT), capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_cache_directory(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    outs = [probe(env, cwd)           # two processes
            for cwd in (ROOT, os.path.join(ROOT, "tests"))]
    assert outs[0] == outs[1]
    directory, set_in_code, _threshold = outs[0]
    if env_dir is None:
        assert directory == os.path.join(ROOT, ".jax_cache")
        assert set_in_code == "True"
    else:
        assert directory == env_dir
        assert set_in_code == "False"


@pytest.mark.parametrize("env_secs,want", [(None, 0.0), ("2.5", 2.5)])
def test_cache_threshold(env_secs, want):
    """Every program is cached (the engine is hundreds of sub-second
    compiles) unless the user's own JAX setting says otherwise."""
    env = {k: v for k, v in os.environ.items() if k != THRESHOLD}
    if env_secs is not None:
        env[THRESHOLD] = env_secs
    assert float(probe(env)[2]) == want
