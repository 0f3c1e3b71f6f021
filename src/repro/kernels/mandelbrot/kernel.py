"""Mandelbrot escape-time as a Pallas TPU kernel.

Hardware adaptation (DESIGN.md): the paper distributes *lines* to worker
JVMs, each running a scalar per-point ``while`` loop.  A TPU has no
per-lane control flow, so the kernel is re-tiled for the VPU:

* the image is blocked into VMEM tiles (BLOCK_H x BLOCK_W, lane-aligned to
  (8, 128) f32 tiling);
* the data-dependent per-point ``while`` becomes a *fixed-trip*
  ``fori_loop`` over ``max_iters`` with a per-lane alive mask — every lane
  does the same work and the mask retires escaped points (the standard SIMD
  escape-time formulation);
* every loop carry is f32 and starts from the input tile (``x0 - x0`` is
  +0.0 for every finite coordinate): the v5e compiler refuses i32 carries
  that start as splat constants ("Invalid relayout ... replicated in
  destination") and bool carries outright.  The mask is 1.0/0.0 and the
  count is exact in f32 up to 2**24 trips.  One cast and one store per
  tile;
* the selects read the carried mask, as the oracle's do.  XLA's CPU
  backend (interpret mode) fuses by that structure and contracts a
  multiply-add into an FMA only inside a fusion, so a body shaped
  differently (e.g. selecting on ``|z|^2 < 4`` directly) rounds some
  boundary points differently from the oracle.

The emit/cluster/collect deployment still distributes tiles across nodes —
the kernel is what one worker core runs per tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_H = 64
BLOCK_W = 256
_F32_EXACT = 2**24  # largest count an f32 carry holds exactly


def _mandelbrot_kernel(x0_ref, y0_ref, iters_ref, colour_ref, *, max_iters: int):
    x0 = x0_ref[...]
    y0 = y0_ref[...]

    def body(_t, state):
        zx, zy, count, alive = state
        zx2 = zx * zx
        zy2 = zy * zy
        alive = jnp.where((zx2 + zy2) < 4.0, alive, 0.0)
        live = alive > 0.0
        new_zx = zx2 - zy2 + x0
        new_zy = 2.0 * zx * zy + y0
        zx = jnp.where(live, new_zx, zx)
        zy = jnp.where(live, new_zy, zy)
        count = count + alive
        return zx, zy, count, alive

    zero = x0 - x0
    init = (zero, zero, zero, zero + 1.0)
    _zx, _zy, count, _alive = jax.lax.fori_loop(0, max_iters, body, init)
    iters = count.astype(jnp.int32)
    iters_ref[...] = iters
    colour_ref[...] = (iters < max_iters).astype(jnp.int32)


def mandelbrot_pallas(
    x0: jax.Array,
    y0: jax.Array,
    max_iters: int,
    *,
    block_h: int = BLOCK_H,
    block_w: int = BLOCK_W,
    interpret: bool = False,
):
    """x0/y0: [H, W] f32 coordinate grids -> (iterations, colour) i32."""
    if max_iters > _F32_EXACT:
        raise ValueError(f"max_iters={max_iters} exceeds the f32 count's "
                         f"exact range ({_F32_EXACT})")
    H, W = x0.shape
    if H % block_h or W % block_w:
        raise ValueError(
            f"grid {H}x{W} must tile by ({block_h},{block_w}); "
            "use ops.mandelbrot for automatic padding"
        )
    grid = (H // block_h, W // block_w)
    spec = pl.BlockSpec((block_h, block_w), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_mandelbrot_kernel, max_iters=max_iters),
        out_shape=(
            jax.ShapeDtypeStruct((H, W), jnp.int32),
            jax.ShapeDtypeStruct((H, W), jnp.int32),
        ),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        interpret=interpret,
    )(x0, y0)
