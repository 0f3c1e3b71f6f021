"""What a metric reader sees of a run, and the run's report on stderr.

Each reader, ``bench/metrics/<metric>.py``, defines ``read(run)`` and
returns a number, or None where the run holds nothing to read (the
harness then leaves the metric out of the result line).  ``run`` is a
:class:`RunView`.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Any

from bench.probes import GC_PAUSE_S

# The Mandelbrot kernel as the device trace names it: the Pallas call of
# ``repro.kernels.mandelbrot.kernel`` has no stable name of its own, and
# shows on the TPU as the custom call ``%mandelbrot.<n> = ... custom-call(``.
KERNEL_EVENT = r"^%mandelbrot(\.\d+)? = .*custom-call\("


@dataclass
class RunView:
    cell: Any  # bench.cell.Cell
    rec: Any  # bench.drive.Recording
    trace: Any = None  # bench.trace_reduce.TraceSummary, traced runs only


def report(run: RunView, result: dict, log) -> None:
    """The run in a few lines, then each number compared with its limit."""
    rec = run.rec
    log(f"cell={run.cell.name} jobs={len(rec.jobs)} "
        f"window_s={rec.window_s} bands_in_window={rec.window_bands()} "
        f"setup_s={rec.setup_s}")
    by_node: dict[str, int] = {}
    for job in rec.jobs:
        for nid, n in (job.items_by_node or {}).items():
            by_node[nid] = by_node.get(nid, 0) + n
    log(f"bands of finished jobs by node: {by_node}")
    errors = [j.error for j in rec.jobs if j.error]
    if errors:
        log(f"jobs without an answer: {len(errors)}; first: {errors[:3]}")
    done = [j.latency_s for j in rec.jobs if j.latency_s is not None]
    if done and len(done) <= 64:
        log(f"job seconds: {[round(s, 4) for s in done]}")
    if done:
        # The slowest jobs far over the median, with what the program says
        # of their time.
        slow = 1.25 * statistics.median(done)
        jobs = [(i, j) for i, j in enumerate(rec.jobs)
                if j.latency_s is not None and j.latency_s > slow]
        jobs.sort(key=lambda ij: -ij[1].latency_s)
        for i, job in sorted(jobs[:10], key=lambda ij: ij[0]):
            log(f"slow job {i}: from {job.due - rec.window_start:.3f} s "
                f"for {job.latency_s:.4f} s, first result "
                f"{job.first_result_ms} ms, code shipped "
                f"{job.code_shipped}, duplicates {job.duplicates}")
    for proc, pauses in (rec.gc_pauses or {}).items():
        inside = [(round(end - rec.window_start, 3), gen, round(took, 4))
                  for end, gen, took in pauses
                  if rec.window_start <= end <= rec.window_end]
        log(f"gc pauses over {GC_PAUSE_S} s in the window, {proc}: "
            f"{len(inside)} {inside[:20]}")
    log(f"device={result['device']}")
    for name, m in result["metrics"].items():
        log(f"metric {name}={m['value']} {m['unit']}")
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']} limit={c['limit']}")
