"""Jitted public wrappers for the Mandelbrot kernel: auto-padding to the
kernel's tiles, and the caller's choice of the compiled Pallas kernel
(default), interpret-mode Pallas, or the jnp oracle; and the farm's band
program, one device call from a band's coordinates to its two sums."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.channels import padded_size
from repro.kernels.mandelbrot.kernel import BLOCK_H, BLOCK_W, mandelbrot_pallas
from repro.kernels.mandelbrot.ref import mandelbrot_reference


@partial(jax.jit, static_argnames=("max_iters", "use_pallas", "interpret"))
def mandelbrot(
    x0: jax.Array,
    y0: jax.Array,
    *,
    max_iters: int = 1000,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Escape-time iterations + colour for a coordinate grid [H, W].

    The compiled kernel needs a TPU; on the CPU pass ``interpret=True``
    (the Pallas interpreter) or ``use_pallas=False`` (the jnp oracle).
    """
    if not use_pallas:
        return mandelbrot_reference(x0, y0, max_iters)
    H, W = x0.shape
    bh = min(BLOCK_H, padded_size(H, 8))
    bw = min(BLOCK_W, padded_size(W, 128))
    Hp, Wp = padded_size(H, bh), padded_size(W, bw)
    if (Hp, Wp) != (H, W):
        # Padding coordinates with 4.0 (outside the set) -> 1 trip, masked off.
        x0 = jnp.pad(x0, ((0, Hp - H), (0, Wp - W)), constant_values=4.0)
        y0 = jnp.pad(y0, ((0, Hp - H), (0, Wp - W)), constant_values=4.0)
    iters, colour = mandelbrot_pallas(
        x0, y0, max_iters, block_h=bh, block_w=bw, interpret=interpret
    )
    return iters[:H, :W], colour[:H, :W]


@partial(jax.jit, static_argnames=("max_iters", "interpret"))
def band_totals(
    x_row: jax.Array,
    y_col: jax.Array,
    *,
    max_iters: int,
    interpret: bool = False,
):
    """One band's iteration and escaped-point sums, as one int32[2].

    ``x_row`` (f32 [1, W]) holds the x of every point of a line and
    ``y_col`` (f32 [H, 1]) the y of each of the band's lines; both are
    broadcast to the band and rendered by :func:`mandelbrot`.  The shapes
    depend on the band alone, never on the job's number of lines, so one
    compile serves every farm of that width.  int32 is exact: a band's
    iterations sum to at most ``H * W * max_iters`` (2.24e7 for the
    paper's 4 x 5600 band at escape 1000).
    """
    shape = (y_col.shape[0], x_row.shape[1])
    iters, colour = mandelbrot(jnp.broadcast_to(x_row, shape),
                               jnp.broadcast_to(y_col, shape),
                               max_iters=max_iters, interpret=interpret)
    return jnp.stack([jnp.sum(iters), jnp.sum(colour)])
