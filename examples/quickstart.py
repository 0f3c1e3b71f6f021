"""Quickstart — the paper's own example, end to end, in both spec APIs.

Part 1 builds the Mandelbrot application from a textual ``.cgpp``
specification (Listing 2 of the paper), verifies the deployment formally
(section 7), prints the generated deployment plan (section 4 / figure 1),
runs it on the chosen backend and reports the paper's counts + per-node
timing (requirement 7).

Part 2 builds the same workload as a *two-stage pipeline* with the fluent
Python API — Mandelbrot lines rendered by stage 1, reduced per line by
stage 2 — and runs it on the same backend: the generalised spec layer with
the paper's network as its one-stage special case.

Run:  PYTHONPATH=src python examples/quickstart.py
      PYTHONPATH=src python examples/quickstart.py cluster   # real subprocesses
(on a CPU-only machine prefix both with REPRO_INTERPRET=1)

Instance sizes are env-tunable (CI's examples-smoke job shrinks them):
QUICKSTART_WIDTH / QUICKSTART_LINES / QUICKSTART_ITERS.  The Mandelbrot
kernel runs compiled, which needs a TPU; ``REPRO_INTERPRET=1`` runs it
in the Pallas interpreter instead, as a CPU-only machine must.
"""

import os
import sys

import jax.numpy as jnp

from repro.core.builder import ClusterBuilder
from repro.core.dsl import Pipeline, parse_cgpp
from repro.core.processes import EmitDetails, ResultDetails
from repro.core.verify import verify_spec
from repro.kernels.mandelbrot.ops import mandelbrot
from repro.kernels.mandelbrot.ref import line_coords

WIDTH = int(os.environ.get("QUICKSTART_WIDTH", "700"))    # paper: 5600
LINES = int(os.environ.get("QUICKSTART_LINES", "400"))    # paper: 3200
MAX_ITERATIONS = int(os.environ.get("QUICKSTART_ITERS", "250"))  # paper: 1000
INTERPRET = os.environ.get("REPRO_INTERPRET", "0") == "1"

SPEC = """
# Mandelbrot DSL specification (paper Listing 2), python-flavoured .cgpp
cores = 4
clusters = 2
max_iterations = %(iters)d
width = %(width)d

//@emit 192.168.1.176
emit_details = DataDetails(
    name="Mdata",
    init=lambda width, iters: (0, %(lines)d),
    init_data=(width, max_iterations),
    create=lambda s: (None, s) if s[0] >= s[1] else (s[0], (s[0] + 1, s[1])),
)
emit = Emit(e_details=emit_details)
onrl = OneNodeRequestedList()

//@cluster clusters
nrfa = NodeRequestingFanAny(destinations=cores)
group = AnyGroupAny(workers=cores, function=CALCULATE)
afoc = AnyFanOne(sources=cores)

//@collect
result_details = ResultDetails(
    name="Mcollect",
    init=lambda: dict(points=0, white=0, black=0, total_iters=0),
    collect=COLLECTOR,
    finalise=lambda acc: acc,
)
afo = AnyFanOne(sources=clusters)
collector = Collect(r_details=result_details)
"""


def calculate(line_y: int):
    """The user's sequential data method (paper Mdata.calculateColour)."""
    x0, y0 = line_coords(WIDTH, line_y)
    iters, colour = mandelbrot(x0[None], y0[None], max_iters=MAX_ITERATIONS,
                               interpret=INTERPRET)
    return {
        "points": WIDTH,
        "white": int(jnp.sum(colour)),
        "total_iters": int(jnp.sum(iters)),
    }


def collector(acc, item):
    acc["points"] += item["points"]
    acc["white"] += item["white"]
    acc["black"] += item["points"] - item["white"]
    acc["total_iters"] += item["total_iters"]
    return acc


def reduce_line(item):
    """Stage-2 work: collapse one line's stats into a compact record."""
    return (item["points"], item["white"], item["total_iters"])


def fluent_pipeline_demo(backend: str) -> None:
    """The same workload as a two-stage pipeline via the fluent API."""
    lines = max(LINES // 4, 8)  # a smaller instance: this is the API demo

    emit = EmitDetails(
        name="Mdata",
        init=lambda n: (0, n),
        init_data=(lines,),
        create=lambda s: (None, s) if s[0] >= s[1] else (s[0], (s[0] + 1, s[1])),
    )

    def fold(acc, item):
        points, white, iters = item
        acc["points"] += points
        acc["white"] += white
        acc["black"] += points - white
        acc["total_iters"] += iters
        return acc

    spec = (Pipeline(host="192.168.1.176")
            .emit(emit)
            .stage(calculate, nodes=2, workers=2, name="render")
            .stage(reduce_line, nodes=1, workers=1, name="reduce")
            .collect(ResultDetails(
                name="Mcollect",
                init=lambda: dict(points=0, white=0, black=0, total_iters=0),
                collect=fold,
            ))
            .build())
    print(f"fluent pipeline: "
          + " -> ".join(f"{st.name}[{st.nclusters}x{st.workers_per_node}]"
                        for st in spec.stages))

    report = verify_spec(spec)
    print(report.summary(), "\n")
    assert report.ok, "the chained network must verify like the single hop"

    builder = ClusterBuilder()
    app = builder.build_application(spec, backend=backend)
    result = app.run()
    print(f"{result['points']}, {result['white']}, {result['black']}, "
          f"{result['total_iters']}")
    assert result["points"] == lines * WIDTH


def main() -> None:
    spec = parse_cgpp(
        SPEC % {"iters": MAX_ITERATIONS, "width": WIDTH, "lines": LINES},
        namespace={"CALCULATE": calculate, "COLLECTOR": collector},
    )
    print(f"parsed spec: {spec.nclusters} nodes x {spec.workers_per_node} workers\n")

    report = verify_spec(spec, num_objects=4)
    print(report.summary(), "\n")
    assert report.ok, "deployment must be provably deadlock/livelock free"

    builder = ClusterBuilder()
    print(builder.deployment_plan(spec).describe(), "\n")

    # "cluster" runs the identical spec over real node-loader subprocesses
    # connected by TCP (repro.cluster, paper §4) instead of threads.
    backend = sys.argv[1] if len(sys.argv) > 1 else "threads"
    app = builder.build_application(spec, backend=backend)
    result = app.run()
    # paper prints: points, whiteCount, blackCount, totalIters
    print(f"{result['points']}, {result['white']}, {result['black']}, "
          f"{result['total_iters']}")
    print()
    print(builder.timing.report())

    print("\n--- fluent two-stage pipeline (same workload, generalised "
          "spec API) ---\n")
    fluent_pipeline_demo(backend)


if __name__ == "__main__":
    main()
