"""repro.compile_cache: the persistent compile cache goes where
JAX_COMPILATION_CACHE_DIR says, else to one fixed directory in the
checkout."""
import json
import time

import pytest

from repro import compile_cache
from tests.util import run_py

_CODE = """
import json, jax, jax.numpy as jnp
from repro import compile_cache
d = compile_cache.enable()
jax.jit(lambda x: jnp.cos(x) * {scale})(jnp.ones(3)).block_until_ready()
print(json.dumps({{"dir": str(d),
                   "config": jax.config.jax_compilation_cache_dir}}))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_entries_land_in_the_chosen_dir(tmp_path, from_env):
    env_dir = tmp_path / "env_cache"
    want = env_dir if from_env else compile_cache.CHECKOUT_DIR
    t0 = time.time()
    # a constant of its own gives this program a cache key no other run has
    scale = float(time.time_ns() % 100_000) + (0.5 if from_env else 0.25)
    res = run_py(_CODE.format(scale=scale), env_extra={
        compile_cache.ENV: str(env_dir) if from_env else ""})
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["config"] == str(want)
    fresh = [p for p in want.iterdir() if p.stat().st_mtime >= t0 - 1]
    assert fresh, f"no cache entry written under {want}"
