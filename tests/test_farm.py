"""The Mandelbrot farm that ``chip_smoke.py`` drives, rehearsed on the CPU:
the same function, at a tiny size, through ``ClusterService`` with the
kernel in the Pallas interpreter, checked against the jnp oracle."""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

from repro.apps.mandelbrot_farm import (
    LINES_PER_ITEM,
    farm_grid,
    farm_totals,
)
from repro.kernels.mandelbrot.ref import grid_coords, line_coords

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_chip_smoke_farm_matches_oracle_on_cpu(chip_smoke):
    lines, width, max_iters = 24, 256, 64
    logged = []
    report = chip_smoke.run_farm(
        lines=lines, width=width, max_iters=max_iters, interpret=True,
        platform="cpu", workers=2,
        log=lambda *a, **_kw: logged.append(" ".join(map(str, a))))
    assert report["device"]["platform"] == "cpu"
    assert report["device"]["first_call_ms"] > 0
    assert report["node_backend_ms"] is not None  # started while booting
    assert report["band_points_differing"] == 0
    assert report["device"]["band_iters"].shape == (LINES_PER_ITEM, width)
    assert report["points_differing"] == 0
    assert report["oracle"]["points"] == lines * width
    cold, warm = report["submits"]
    assert cold["items_per_node"] == {"node0": lines // LINES_PER_ITEM}
    assert warm["items_per_node"] == {"node0": lines // LINES_PER_ITEM}
    assert (cold["code_shipped"], warm["code_shipped"]) == (1, 0)
    assert any(line.startswith("submit 2 (warm)") for line in logged)


def test_chip_smoke_without_tpu_exits_nonzero_and_prints_no_result(
        chip_smoke, capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # its node sees no TPU
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_farm_totals_sum_iterations_past_int32():
    """The paper grid's iteration sum can reach 1.79e10: totals are exact
    Python ints, not an int32 that wraps."""
    rows, width, max_iters = 3200, 5600, 1000
    iters = np.full((rows, width), max_iters, np.int32)
    colour = np.zeros((rows, width), np.int32)
    assert farm_totals(iters, colour) == (rows * width * max_iters, 0,
                                          rows * width)
    assert rows * width * max_iters > np.iinfo(np.int32).max
    x0, y0 = farm_grid(66, 100)  # whole bands only: 66 lines -> 16 bands
    assert x0.shape == y0.shape == (64, 100)


def test_band_coords_tile_the_whole_grid():
    """A band's coordinates are bit-identical to the same lines of the
    whole grid, which is what lets the farm match the oracle exactly."""
    x, y = grid_coords(12, 5600)
    for first in (0, 4, 8):
        bx, by = grid_coords(LINES_PER_ITEM, 5600, first_line=first)
        np.testing.assert_array_equal(bx, x[first:first + LINES_PER_ITEM])
        np.testing.assert_array_equal(by, y[first:first + LINES_PER_ITEM])
    lx, ly = line_coords(5600, 7)
    np.testing.assert_array_equal(lx, x[7])
    np.testing.assert_array_equal(ly, y[7])
    assert x.dtype == y.dtype == np.float32
