"""Compile the fused ERM kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel entry point at a real width and
compiles it with the TPU compiler for a chip that is described, not
attached, which refuses what interpret mode accepts (unaligned slices,
block shapes off the (8, 128) tiling).  The topology is described inside a
fixture, so only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_erm

# (rows, features, batch): the HIGGS shape of chip_smoke.py, a
# lane-aligned width that splits into feature tiles, and the epsilon shape
# of the benchmark (one tile of 2,000 lanes, padded to 2,048 in HBM)
SHAPES = {"higgs-n28": (11_000_000, 28, 1000),
          "wide-n2048": (65_536, 2048, 1000),
          "epsilon-n2000": (400_000, 2000, 1000)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _entry(name, b):
    if name == "grad_block":
        return (lambda X, y, w, s: fused_erm.fused_grad_block(
            X, y, w, s, loss="logistic", batch_size=b, interpret=False),
            ("X", "y", "w", "start"))
    if name == "margins_block":
        return (lambda X, w, s: fused_erm.fused_margins_block(
            X, w, s, batch_size=b, interpret=False), ("X", "w", "start"))
    if name == "grad_rows":
        return (lambda X, y, w, i: fused_erm.fused_grad_rows(
            X, y, w, i, loss="logistic", interpret=False),
            ("X", "y", "w", "idx"))
    return (lambda X, w, i: fused_erm.fused_margins_rows(
        X, w, i, interpret=False), ("X", "w", "idx"))


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("entry", ["grad_block", "margins_block",
                                   "grad_rows", "margins_rows"])
def test_fused_kernel_compiles_for_v5e(one_chip, entry, shape):
    l, n, b = SHAPES[shape]
    avals = {"X": ((l, n), jnp.float32), "y": ((l,), jnp.float32),
             "w": ((n,), jnp.float32), "start": ((), jnp.int32),
             "idx": ((b,), jnp.int32)}
    fn, args = _entry(entry, b)
    sds = [jax.ShapeDtypeStruct(*avals[a], sharding=one_chip) for a in args]
    compiled = jax.jit(fn).lower(*sds).compile()
    assert "tpu_custom_call" in compiled.as_text()
