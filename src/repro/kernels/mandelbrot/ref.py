"""Pure-jnp oracle for the Mandelbrot escape-time computation.

This is the paper's workload (Appendix B, ``Mdata.calculateColour``): for
each point c = x + iy iterate z <- z^2 + c until |z|^2 >= 4 or the escape
value is reached.  The oracle mirrors the paper's loop exactly, vectorised:
``iterations`` counts loop trips (capped at ``max_iters``) and ``colour`` is
WHITE (1) when the point escaped, BLACK (0) otherwise — the paper's
convention {4:53}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def mandelbrot_reference(x0: jax.Array, y0: jax.Array, max_iters: int):
    """x0, y0: f32 arrays of identical shape -> (iterations i32, colour i32)."""
    shape = x0.shape

    def body(_t, state):
        zx, zy, iters, alive = state
        zx2 = zx * zx
        zy2 = zy * zy
        alive_now = alive & ((zx2 + zy2) < 4.0)
        new_zx = zx2 - zy2 + x0
        new_zy = 2.0 * zx * zy + y0
        zx = jnp.where(alive_now, new_zx, zx)
        zy = jnp.where(alive_now, new_zy, zy)
        iters = iters + alive_now.astype(jnp.int32)
        return zx, zy, iters, alive_now

    zx = jnp.zeros(shape, jnp.float32)
    zy = jnp.zeros(shape, jnp.float32)
    iters = jnp.zeros(shape, jnp.int32)
    alive = jnp.ones(shape, bool)
    zx, zy, iters, alive = jax.lax.fori_loop(
        0, max_iters, body, (zx, zy, iters, alive)
    )
    colour = (iters < max_iters).astype(jnp.int32)  # WHITE=1 escaped
    return iters, colour


def row_xs(width: int, *, min_x=-2.5, range_x=3.5):
    """The x coordinate of each of a line's ``width`` points, in f32: the
    same for every line of the paper's layout {4:26-39}."""
    delta = range_x / width
    return (np.float32(min_x)
            + np.arange(width, dtype=np.float32) * np.float32(delta))


def line_ys(height: int, width: int, *, first_line: int = 0, min_y=1.0,
            range_x=3.5):
    """The y coordinate of lines ``first_line .. first_line + height - 1``
    of the paper's layout, in f32: computed in f64, then cast, so a band's
    values equal the same lines' of the whole grid bit for bit."""
    delta = range_x / width
    lines = np.arange(first_line, first_line + height)
    return (min_y - lines * delta).astype(np.float32)


def grid_coords(height: int, width: int, *, first_line: int = 0,
                min_x=-2.5, min_y=1.0, range_x=3.5):
    """The paper's ``createInstance`` coordinate layout {4:26-39} for lines
    ``first_line .. first_line + height - 1``: two f32 [height, width] NumPy
    grids of :func:`row_xs` and :func:`line_ys`.  Built on the host so that
    every band of a farm and the whole grid of its oracle get bit-identical
    coordinates on any device."""
    x = row_xs(width, min_x=min_x, range_x=range_x)
    y = line_ys(height, width, first_line=first_line, min_y=min_y,
                range_x=range_x)
    return (np.broadcast_to(x, (height, width)).copy(),
            np.broadcast_to(y[:, None], (height, width)).copy())


def line_coords(width: int, line_y: int, **kw):
    """One line of :func:`grid_coords`: two f32 [width] arrays."""
    x, y = grid_coords(1, width, first_line=line_y, **kw)
    return x[0], y[0]
