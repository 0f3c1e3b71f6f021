"""The paper's Mandelbrot farm (§8) as an emit → cluster → collect spec.

Emit hands out band numbers; a worker renders one band of
``LINES_PER_ITEM`` lines and returns ``(iterations, white, points)``;
collect sums the triples in Python ints.  The paper's instance is 3200
lines x 5600 points with escape value 1000: 800 bands and 17,920,000
points, whose iteration sum can reach 1.79e10 and so never fits an int32.

A band is one device program,
:func:`repro.kernels.mandelbrot.ops.band_totals`: the line's x values stay
on the node's device (:func:`device_x_row`, put there once per width), the
band's ``LINES_PER_ITEM`` y values go up (16 B), the Pallas kernel renders
the band and both sums come back in one int32[2].  Its shapes depend on
the band alone, so the first job of a width compiles what every later job
of that width runs, whatever its number of lines.

Whether the kernel runs compiled or in the Pallas interpreter is the
caller's choice (``interpret=``); nothing here looks at the backend.
"""

from __future__ import annotations

import threading

from repro.core.dsl import ClusterSpec
from repro.core.processes import EmitDetails, ResultDetails

PAPER_LINES = 3200
PAPER_WIDTH = 5600
PAPER_MAX_ITERS = 1000
LINES_PER_ITEM = 4  # one work object = a band of lines (paper: 1 line)


def farm_spec(nclusters: int, workers: int, *, lines: int, width: int,
              max_iters: int, interpret: bool) -> ClusterSpec:
    """The farm over ``lines // LINES_PER_ITEM`` bands of ``width`` points."""
    lines_per_item = LINES_PER_ITEM

    def init(n_items):
        return (0, n_items)

    def create(state):
        i, n = state
        if i >= n:
            return None, state
        return i, (i + 1, n)

    def work(item: int):
        import numpy as np

        # Imported on the node, not captured: this closure ships by value
        # with every job, and the node's module keeps the row cache.
        from repro.apps.mandelbrot_farm import device_x_row
        from repro.cluster.telemetry.spans import span
        from repro.kernels.mandelbrot.ops import band_totals
        from repro.kernels.mandelbrot.ref import line_ys

        # The band program's three steps, as children of the node's
        # ``node.work`` span: the band's y values (16 B for 4 lines), the
        # one device call (dispatch), and its two sums read back at once
        # (which waits for the device).
        with span("band.coords"):
            y_col = line_ys(lines_per_item, width,
                            first_line=item * lines_per_item)[:, None]
        with span("band.call"):
            totals = band_totals(device_x_row(width), y_col,
                                 max_iters=max_iters, interpret=interpret)
        with span("band.readback"):
            iters, white = np.asarray(totals).tolist()
            return (iters, white, lines_per_item * width)

    def collect(acc, item):
        t, w, p = item
        return (acc[0] + t, acc[1] + w, acc[2] + p)

    return ClusterSpec.simple(
        host="127.0.0.1",
        nclusters=nclusters,
        workers_per_node=workers,
        emit_details=EmitDetails(
            name="Mdata", init=init, init_data=(lines // lines_per_item,),
            create=create,
        ),
        work_function=work,
        result_details=ResultDetails(
            name="Mcollect", init=lambda: (0, 0, 0), collect=collect,
        ),
    )


_X_ROWS: dict = {}  # width -> f32 [1, width] on this process's device
_X_ROWS_LOCK = threading.Lock()


def device_x_row(width: int):
    """The x of every point of a line (``ref.row_xs``) as an f32
    [1, width] array on this process's default device, put there once per
    width and shared by every worker thread.
    Each upload counts in the ``band_row_uploads`` counter, which a node's
    heartbeat carries: more than one a width means the cache is not
    holding."""
    row = _X_ROWS.get(width)
    if row is not None:
        return row
    with _X_ROWS_LOCK:
        row = _X_ROWS.get(width)
        if row is None:
            import jax

            from repro.cluster.telemetry import counters
            from repro.kernels.mandelbrot.ref import row_xs

            row = _X_ROWS[width] = jax.device_put(row_xs(width)[None, :])
            counters.add("band_row_uploads")
    return row


def warm_spec(*, width: int, max_iters: int, interpret: bool,
              first_line: int = 0, platform: str | None = None
              ) -> ClusterSpec:
    """A one-item job that reports the device of the node that runs it and
    renders there the band of ``LINES_PER_ITEM`` lines from ``first_line``
    with the kernel itself, point by point (the farm's band program returns
    only the band's sums): the first call at the band shape is timed as
    ``first_call_ms`` (trace + compile + one band), and the band's
    iterations come back as ``band_iters`` for a point-by-point check.

    With ``platform``, a node whose first device is another platform skips
    the kernel and only reports, so a caller can refuse it quickly."""
    lines_per_item = LINES_PER_ITEM

    def create(state):
        return (None, state) if state else (0, True)

    def probe(_item):
        import os
        import time

        import jax
        import numpy as np

        from repro.kernels.mandelbrot.ops import mandelbrot
        from repro.kernels.mandelbrot.ref import grid_coords

        devices = jax.devices()
        report = {"platform": devices[0].platform,
                  "device_kind": devices[0].device_kind,
                  "device_count": len(devices),
                  "pid": os.getpid(),
                  "first_call_ms": None, "band_iters": None}
        if platform is not None and report["platform"] != platform:
            return report
        x0, y0 = grid_coords(lines_per_item, width, first_line=first_line)
        t0 = time.perf_counter()
        iters, _ = jax.block_until_ready(
            mandelbrot(x0, y0, max_iters=max_iters, interpret=interpret))
        report["first_call_ms"] = (time.perf_counter() - t0) * 1e3
        report["band_iters"] = np.asarray(iters)
        return report

    return ClusterSpec.simple(
        host="127.0.0.1", nclusters=1, workers_per_node=1,
        emit_details=EmitDetails(name="warm", init=lambda: False,
                                 create=create),
        work_function=probe,
        result_details=ResultDetails(name="report", init=lambda: None,
                                     collect=lambda _acc, r: r),
    )


def farm_grid(lines: int, width: int):
    """The coordinates of every point the farm renders: whole bands only,
    as two f32 [rows, width] NumPy grids."""
    from repro.kernels.mandelbrot.ref import grid_coords

    return grid_coords((lines // LINES_PER_ITEM) * LINES_PER_ITEM, width)


def farm_totals(iters, colour) -> tuple[int, int, int]:
    """A grid's ``(iterations, white, points)`` as the farm's collect sums
    them, in Python ints: iterations summed per line in int32 (at most
    ``width * max_iters``) and across lines in int64."""
    import numpy as np

    per_line = np.asarray(iters.sum(axis=1), dtype=np.int64)
    return (int(per_line.sum()), int(colour.sum()), int(colour.size))
