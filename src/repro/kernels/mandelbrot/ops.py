"""Jitted public wrapper for the Mandelbrot kernel: auto-padding to the
kernel's tiles, and the caller's choice of the compiled Pallas kernel
(default), interpret-mode Pallas, or the jnp oracle."""

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
