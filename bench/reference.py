"""The plain reference of the Mandelbrot farm, kept apart from the program.

It follows the paper's own loop (arXiv 2206.04429, Appendix B,
``calculateColour``): for each point c = x0 + i*y0, iterate
z <- z^2 + c while |z|^2 < radius^2 and fewer than ``max_iters`` trips
have run; the point's count is the number of trips, and it is white when
it escaped (count < ``max_iters``).  The plane layout is the paper's
``createInstance``: line ``l`` of a ``width``-point plane lies at
y = min_y - l * delta, point ``p`` at x = min_x + p * delta, with
delta = range_x / width.

Nothing here imports the program.  The loop runs in jax.numpy over blocks
of lines on whatever device the process holds, in the precision it is
asked for: ``float32`` is the reference, ``bfloat16`` the control (the
next precision below the configuration's).
"""

from __future__ import annotations

import functools

import numpy as np

# Lines per device block: the reference runs block by block so that the
# largest plane (16000 x 16000) never needs more than a few hundred MB.
BLOCK_POINTS = 1 << 24


def plane_axes(first_line: int, lines: int, width: int, plane: dict):
    """The f32 x coordinates of one line ([width]) and the f32 y of each
    of ``lines`` lines from ``first_line`` ([lines]), as the paper lays
    the plane out (y computed in float64, then rounded to f32)."""
    delta = plane["range_x"] / width
    x = (np.float32(plane["min_x"])
         + np.arange(width, dtype=np.float32) * np.float32(delta))
    rows = np.arange(first_line, first_line + lines)
    y = (plane["min_y"] - rows * delta).astype(np.float32)
    return x, y


@functools.lru_cache(maxsize=None)
def _block_fn(max_iters: int, radius: float, dtype: str):
    """The loop over one block of lines, jitted once per precision."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    limit = radius * radius

    @jax.jit
    def block(x, y):
        shape = (y.shape[0], x.shape[0])
        x0 = jnp.broadcast_to(x.astype(dt)[None, :], shape)
        y0 = jnp.broadcast_to(y.astype(dt)[:, None], shape)

        def body(_t, state):
            zx, zy, count, alive = state
            zx2 = zx * zx
            zy2 = zy * zy
            alive = alive & ((zx2 + zy2) < limit)
            new_zx = zx2 - zy2 + x0
            new_zy = 2.0 * zx * zy + y0
            zx = jnp.where(alive, new_zx, zx)
            zy = jnp.where(alive, new_zy, zy)
            return zx, zy, count + alive.astype(jnp.int32), alive

        zero = jnp.zeros(shape, dt)
        state = (zero, zero, jnp.zeros(shape, jnp.int32),
                 jnp.ones(shape, bool))
        _zx, _zy, count, _alive = jax.lax.fori_loop(0, max_iters, body,
                                                    state)
        # Per line: at most width * max_iters, well inside int32.
        return count.sum(axis=1), (count < max_iters).sum(axis=1)

    return block


def farm_answer(*, lines: int, width: int, max_iters: int,
                escape_radius: float, plane: dict, lines_per_band: int,
                dtype: str = "float32") -> tuple[int, int, int]:
    """What a farm over ``lines // lines_per_band`` whole bands of the
    plane must return: ``(iterations, white, points)`` in Python ints."""
    rows = (lines // lines_per_band) * lines_per_band
    block_lines = max(1, min(rows, BLOCK_POINTS // width))
    block = _block_fn(max_iters, escape_radius, dtype)
    iterations = white = 0
    for first in range(0, rows, block_lines):
        n = min(block_lines, rows - first)
        x, y = plane_axes(first, block_lines, width, plane)
        # A ragged last block keeps the block's shape (one compile); its
        # extra lines are computed and dropped.
        it, wh = block(x, y)
        it = np.asarray(it)[:n]
        wh = np.asarray(wh)[:n]
        iterations += int(it.astype(np.int64).sum())
        white += int(wh.astype(np.int64).sum())
    return iterations, white, rows * width


def band_answer(band: int, *, lines_per_band: int, width: int,
                max_iters: int, escape_radius: float, plane: dict,
                dtype: str) -> tuple[int, int, int]:
    """One band's ``(iterations, white, points)``: the work function of
    the control, which puts this loop in the kernel's place on the node."""
    x, y = plane_axes(band * lines_per_band, lines_per_band, width, plane)
    it, wh = _block_fn(max_iters, escape_radius, dtype)(x, y)
    return (int(np.asarray(it).astype(np.int64).sum()),
            int(np.asarray(wh).sum()), lines_per_band * width)

