"""The control of ``correct``: the reference, in the kernel's place, one
precision below the configuration's.

Each band of the farm is computed on the node by the reference's loop in
bfloat16 (the configuration states float32), and the jobs go through the
same pool, window and judge as the program's.  The judge has to find them
wrong; ``bench/tests/test_bench_faults.py`` keeps that at a CPU size, and
on the chip it runs at the cell's own size:

    python3 bench/run_control.py --workload <name> --seed <n> \
        --seconds <s>
"""

from __future__ import annotations

import functools

CONTROL_DTYPE = "bfloat16"


def _first(n_items):
    return (0, n_items)


def _next(state):
    i, n = state
    return (None, state) if i >= n else (i, (i + 1, n))


def _add(acc, item):
    return tuple(a + b for a, b in zip(acc, item))


def control_spec(config: dict, lines: int):
    """A farm over ``lines`` lines whose bands the bf16 reference renders."""
    from repro.core.dsl import ClusterSpec
    from repro.core.processes import EmitDetails, ResultDetails

    from bench import reference

    work = functools.partial(
        reference.band_answer, lines_per_band=config["lines_per_band"],
        width=config["width"], max_iters=config["max_iters"],
        escape_radius=config["escape_radius"], plane=config["plane"],
        dtype=CONTROL_DTYPE)
    return ClusterSpec.simple(
        host="127.0.0.1", nclusters=1, workers_per_node=config["workers"],
        emit_details=EmitDetails(name="control", init=_first,
                                 init_data=(lines // config["lines_per_band"],),
                                 create=_next),
        work_function=work,
        result_details=ResultDetails(name="control",
                                     init=lambda: (0, 0, 0), collect=_add),
    )
