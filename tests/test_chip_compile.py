"""The main path's kernel compiles for a TPU v5e that is described, not
attached: the Mandelbrot kernel at the farm's band shape and at the paper's
whole grid, and the farm's band program, with the paper's escape value.

The topology is described inside a module fixture, never while a module is
imported, so every test worker collects the same tests and only the one
given this file loads the TPU compiler.  The persistent compilation cache
is off around these compiles: an entry written for a described chip cannot
be read back without one.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from bench.readout import KERNEL_EVENT

from repro.apps.mandelbrot_farm import (
    LINES_PER_ITEM,
    PAPER_LINES,
    PAPER_MAX_ITERS,
    PAPER_WIDTH,
)
from repro.kernels.mandelbrot.ops import band_totals, mandelbrot


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


def test_band_program_compiles_for_v5e_with_one_kernel_call(
        one_chip, no_persistent_cache):
    """The band program at the paper's band holds the kernel as exactly one
    custom call under the name the benchmark's kernel metrics look for in
    the device trace."""
    x_row = jax.ShapeDtypeStruct((1, PAPER_WIDTH), jnp.float32,
                                 sharding=one_chip)
    y_col = jax.ShapeDtypeStruct((LINES_PER_ITEM, 1), jnp.float32,
                                 sharding=one_chip)
    compiled = band_totals.lower(x_row, y_col, max_iters=PAPER_MAX_ITERS,
                                 interpret=False).compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if re.match(KERNEL_EVENT, line.strip())]
    assert len(kernels) == 1, kernels
    assert "tpu_custom_call" in kernels[0]
