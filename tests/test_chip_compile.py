"""The main path's kernel compiles for a TPU v5e that is described, not
attached: the Mandelbrot kernel at the farm's band shape and at the paper's
whole grid, with the paper's escape value.

The topology is described inside a module fixture, never while a module is
imported, so every test worker collects the same tests and only the one
given this file loads the TPU compiler.  The persistent compilation cache
is off around these compiles: an entry written for a described chip cannot
be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.apps.mandelbrot_farm import (
    LINES_PER_ITEM,
    PAPER_LINES,
    PAPER_MAX_ITERS,
    PAPER_WIDTH,
)
from repro.kernels.mandelbrot.ops import mandelbrot


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
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("shape", [(LINES_PER_ITEM, PAPER_WIDTH),
                                   (PAPER_LINES, PAPER_WIDTH)],
                         ids=["band", "paper_grid"])
def test_mandelbrot_compiles_for_v5e(shape, one_chip, no_persistent_cache):
    coords = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda x0, y0: mandelbrot(x0, y0, max_iters=PAPER_MAX_ITERS,
                                  interpret=False)
    ).lower(coords, coords).compile()
    assert "tpu_custom_call" in compiled.as_text()
