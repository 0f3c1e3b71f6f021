"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialisation.

Target: TPU v5e pods.  Single pod = 16 x 16 = 256 chips ("data", "model");
multi-pod = 2 x 16 x 16 = 512 chips ("pod", "data", "model") — the "pod"
axis crosses DCN, which is why the rules put only batch (gradient
all-reduce) on it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (roofline denominators).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
HBM_BYTES = 16 * 2**30  # per chip


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (1 device unless XLA_FLAGS raised it)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def model_axis_size(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
