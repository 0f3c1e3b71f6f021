"""Record the program's spans beside the node's device trace, on a TPU.

    python3 bench/tests/record_spans.py fixture <prefix>
    python3 bench/tests/record_spans.py slice <out-dir>
    python3 bench/tests/record_spans.py hunt <out-dir> [seconds]
    python3 bench/tests/record_spans.py cost

from the root of a checkout, on a machine with a TPU (``cost`` runs
anywhere).  Every mode but ``cost`` boots one warm pool of the paper
configuration (one node-loader, 4 workers, the compiled kernel), as a run
of the benchmark does, and turns spans on in this process and in the node
with one-item jobs (``spans.enable``; ``spans.disable`` and
``spans.drain`` after).

- ``fixture``: a farm of BANDS bands under the node's profiler, started and
  stopped as a traced run of the benchmark does; writes
  ``<prefix>.xplane.pb.gz`` and ``<prefix>.json`` (the band count, the
  answer, and each process's drained spans), the small recording that
  ``test_program_spans.py`` maps onto the trace.
- ``slice``: the cell ``mandelbrot_paper.batch`` in a closed loop with the
  node traced for TRACE_S seconds from TRACE_LEAD_S in, as a traced run of
  the benchmark slices its window, and spans on around that slice; prints
  the device numbers, the five span numbers of ``program_spans.METRICS``,
  ``program_gaps`` and the self-time table, and keeps the trace and the
  spans in ``<out-dir>``.
- ``hunt``: the same closed loop for ``seconds`` (default 51) with spans on
  throughout and no profiler, drained every DRAIN_S seconds; prints where
  the time of each job far slower than the median went.
- ``cost``: the time of a span with recording off and on, in a loop.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import pathlib
import shutil
import statistics
import sys
import tempfile
import threading
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
BANDS = 12
TRACE_LEAD_S = 1.0
TRACE_S = 4.0
DRAIN_S = 5.0
SLOW = 1.25  # a job this many times the median is looked into
# The checkout whose cell is run, the cell, and the platform the node must
# report (a rehearsal on the CPU points these at a tiny configuration).
CELL_ROOT = ROOT
CELL = "mandelbrot_paper.batch"
PLATFORM: str | None = "tpu"

if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def _pool():
    from bench import drive, run_cell
    from bench.cell import load_cell
    from repro.cluster.service import ClusterService

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_cell.cache_dir(ROOT))
    cell = load_cell(CELL_ROOT, CELL)
    svc = ClusterService(nodes=1, workers=cell.config["workers"],
                         preload=drive.PRELOAD)
    return cell, svc


def _call(svc, work, item=0):
    from bench import probes

    return svc.submit(probes.one_item_spec(work, item), priority=1,
                      timeout=120).result()


def _spans_on(svc) -> None:
    from repro.cluster.telemetry import spans

    spans.enable()
    _call(svc, spans.enable)


def _spans_off(svc) -> dict:
    """Off in the node, then here; each process's drained spans."""
    from repro.cluster.telemetry import spans

    _call(svc, spans.disable)
    node = _call(svc, spans.drain)
    spans.disable()
    return {"node": node, "host": spans.drain()}


def _trace_file(trace_dir: str) -> str:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return path


def fixture(prefix: str) -> int:
    from bench import drive, probes

    cell, svc = _pool()
    config = cell.config
    trace_dir = tempfile.mkdtemp(prefix="bench-fixture-")
    try:
        with svc:
            drive.boot(svc, cell, drive.job_spec(config, 16),
                       platform=PLATFORM, device_kinds=None, log=print)
            _spans_on(svc)
            _call(svc, probes.start_trace, trace_dir)
            result = svc.submit(drive.job_spec(config, 4 * BANDS),
                                timeout=60).result()
            _call(svc, probes.stop_trace)
            drained = _spans_off(svc)
        with open(_trace_file(trace_dir), "rb") as src, \
                gzip.open(f"{prefix}.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    with open(f"{prefix}.json", "w") as f:
        json.dump({"bands": BANDS, "result": list(result),
                   "spans": drained}, f, separators=(",", ":"))
    print(json.dumps({"bands": BANDS, "result": list(result),
                      "spans": {p: len(r) for p, (r, _) in drained.items()},
                      "dropped": {p: d for p, (_, d) in drained.items()}}))
    return 0


def _closed_loop(svc, cell, seconds: float, during=None):
    """The benchmark's closed loop for ``seconds``; ``during(start)`` runs
    on this thread meanwhile.  Returns the jobs."""
    from bench import drive, traffic_gen

    config = cell.config
    lines = traffic_gen.job_lines(cell.traffic, config)
    bands = lines // config["lines_per_band"]
    spec = drive.job_spec(config, lines)
    drive.boot(svc, cell, drive.job_spec(
        config, config["workers"] * config["lines_per_band"]),
        platform=PLATFORM, device_kinds=None, log=print)
    jobs: list = []
    start = time.monotonic()
    loop = threading.Thread(target=drive.closed_loop, args=(
        svc, spec, lines, bands, start + seconds, jobs), daemon=True)
    loop.start()
    result = during(start) if during is not None else None
    loop.join()
    for job in jobs:
        if job.handle is not None:
            job.handle.wait(120)
            job.done_at = time.monotonic()
            job.finish()
    return jobs, start, result


def _compiles(svc) -> dict:
    counts = svc.metrics_snapshot()["nodes"]["node0"].get("compiles") or {}
    return {k: counts.get(k) for k in ("jaxpr_traces", "backend_compiles")}


def _print_jobs(jobs, start) -> None:
    done = [(j.due - start, j.latency_s) for j in jobs if j.latency_s]
    print("jobs (start s, seconds):", [(round(a, 3), round(b, 4))
                                       for a, b in done])


def slice_(out_dir: str) -> int:
    from bench import probes, program_spans
    from bench.readout import KERNEL_EVENT

    cell, svc = _pool()
    trace_dir = tempfile.mkdtemp(prefix="bench-slice-")
    os.makedirs(out_dir, exist_ok=True)

    def during(start):
        time.sleep(max(0.0, start + TRACE_LEAD_S - time.monotonic()))
        before = _compiles(svc)
        _spans_on(svc)
        _call(svc, probes.start_trace, trace_dir)
        time.sleep(max(0.0, start + TRACE_LEAD_S + TRACE_S
                       - time.monotonic()))
        _call(svc, probes.stop_trace)
        drained = _spans_off(svc)
        return before, _compiles(svc), drained

    try:
        with svc:
            jobs, start, (before, after, drained) = _closed_loop(
                svc, cell, TRACE_LEAD_S + TRACE_S + 1.5, during)
        with open(_trace_file(trace_dir), "rb") as src, gzip.open(
                os.path.join(out_dir, "slice.xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "slice_spans.json"), "w") as f:
        json.dump(drained, f, separators=(",", ":"))
    summary, start_ns = program_spans.read_file(
        os.path.join(out_dir, "slice.xplane.pb.gz"))
    ps = program_spans.map_spans(drained, start_ns, summary.window)
    _print_jobs(jobs, start)
    gaps = summary.launch_gaps_ns(KERNEL_EVENT)
    kernels = summary.kernel_events(KERNEL_EVENT)
    out = {
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "device_idle_share": 100.0 * summary.idle_share,
        "kernel_us_per_band": sum(k.end - k.start for k in kernels)
        / len(kernels) / 1e3,
        "launch_gap_us.p50": statistics.median(gaps) / 1e3,
        "spans": len(ps.spans), "dropped": ps.dropped,
        "spans_inside": len(ps.inside()),
        "compiles": {"before": before, "after": after},
        "metrics": {name: fn(ps, cell.config["workers"])
                    for name, fn in program_spans.METRICS.items()},
        "program_gaps": program_spans.program_gaps(summary, ps),
        "no_program_span_share": no_span_share(summary, ps),
        "idle_gaps": summary.idle_gaps(),
        "device_ops": summary.device_ops(),
    }
    print("self time over the slice (name, count, total s, self s):")
    for row in program_spans.self_time_table(ps):
        print(f"  {row[0]:<16} {row[1]:>7} {row[2]:.6f} {row[3]:.6f}")
    _check_clock(summary, ps, kernels)
    explain_slow(drained)
    print(json.dumps(out))
    return 0


def no_span_share(summary, ps) -> float:
    """The share of the window's device-idle time that no program span
    covers, in %."""
    from bench.program_spans import NO_PROGRAM_SPAN, program_gaps

    gaps = dict(program_gaps(summary, ps, top=10**6))
    return 100.0 * gaps.get(NO_PROGRAM_SPAN, 0.0) / sum(gaps.values())


def _check_clock(summary, ps, kernels) -> None:
    """How far the kernel event farthest from any mapped ``node.work``
    span lies outside it."""
    from bench.program_spans import outside_ns

    worst = max(outside_ns(ps, kernels))
    print(f"clock: {len(kernels)} kernel events, the farthest lies "
          f"{worst / 1e3:.3f} us outside a mapped node.work span")


def hunt(out_dir: str, seconds: float = 51.0) -> int:
    """Spans on for the whole closed loop; where the slow jobs' time went."""
    cell, svc = _pool()
    os.makedirs(out_dir, exist_ok=True)

    def during(start):
        from repro.cluster.telemetry import spans

        records = {"node": [], "host": []}
        dropped = {"node": 0, "host": 0}

        def keep(proc, drained):
            records[proc] += drained[0]
            dropped[proc] += drained[1]

        _spans_on(svc)
        while time.monotonic() < start + seconds:
            time.sleep(DRAIN_S)
            keep("node", _call(svc, spans.drain))
            keep("host", spans.drain())
        for proc, drained in _spans_off(svc).items():
            keep(proc, drained)
        return {proc: (records[proc], dropped[proc]) for proc in records}

    with svc:
        jobs, start, drained = _closed_loop(svc, cell, seconds, during)
    with open(os.path.join(out_dir, "hunt_spans.json"), "w") as f:
        json.dump(drained, f, separators=(",", ":"))
    _print_jobs(jobs, start)
    print(json.dumps({"dropped": {p: d for p, (_, d) in drained.items()},
                      "spans": {p: len(r) for p, (r, _) in
                                drained.items()}}))
    explain_slow(drained)
    return 0


def explain_slow(drained: dict) -> None:
    """For each farm job far slower than the median (by its host spans:
    first ``host.dispatch`` to last ``host.collect``), where its time went:
    its longest band, longest queue wait and hold, and the longest stretch
    in which none of its bands was in a worker."""
    host, node = drained["host"][0], drained["node"][0]
    span_of: dict[int, list[float]] = {}
    for name, start, end, *_rest, ids in host:
        if name in ("host.dispatch", "host.collect") and "job" in ids:
            lo_hi = span_of.setdefault(ids["job"], [start, end])
            lo_hi[0], lo_hi[1] = min(lo_hi[0], start), max(lo_hi[1], end)
    bands = {j: 0 for j in span_of}
    for name, *_rest, ids in node:
        if name == "node.work" and ids.get("job") in bands:
            bands[ids["job"]] += 1
    farm = {j: (hi - lo) / 1e9 for j, (lo, hi) in span_of.items()
            if bands[j] > 1}  # the probes are jobs of one item
    if not farm:
        print("no farm job in the spans")
        return
    median = statistics.median(farm.values())
    print(f"farm jobs: {len(farm)}, median {median:.4f} s")
    for job, took in sorted(farm.items()):
        if took < SLOW * median:
            continue
        mine = {name: [] for name in ("node.queue", "node.work",
                                      "node.hold", "band.coords",
                                      "band.call", "band.readback")}
        for name, start, end, *_rest, ids in node:
            if name in mine and ids.get("job") == job:
                mine[name].append((start, end, ids.get("item")))
        work = sorted(mine["node.work"])
        stretch, at, covered_to = 0, None, work[0][1] if work else 0
        for s, e, _ in work[1:]:
            if s - covered_to > stretch:
                stretch, at = s - covered_to, covered_to
            covered_to = max(covered_to, e)
        lo = span_of[job][0]
        longest = {name: max(((e - s) / 1e6, item, round((s - lo) / 1e9, 4))
                             for s, e, item in v) if v else None
                   for name, v in mine.items()}
        # What every span of both processes did in the longest stretch.
        during = {}
        if at is not None:
            for proc, records in (("node", node), ("host", host)):
                for name, start, end, *_rest in records:
                    overlap = min(end, at + stretch) - max(start, at)
                    if overlap > 0:
                        key = f"{proc}:{name}"
                        during[key] = during.get(key, 0) + overlap / 1e6
        print(json.dumps({
            "job": job, "seconds": round(took, 4),
            "longest_ms (ms, item, at s)": longest,
            "no_band_in_a_worker_ms": stretch / 1e6,
            "at_s": None if at is None else round((at - lo) / 1e9, 4),
            "spans_in_that_stretch_ms": during}))


def cost(n: int = 200_000) -> int:
    """ns per span, recording off and on, one thread."""
    from repro.cluster.telemetry import spans

    def loop(ids: bool) -> float:
        t0 = time.perf_counter_ns()
        if ids:
            for i in range(n):
                with spans.span("node.work", job=1, item=i):
                    pass
        else:
            for _ in range(n):
                with spans.span("band.call"):
                    pass
        return (time.perf_counter_ns() - t0) / n

    def empty() -> float:
        t0 = time.perf_counter_ns()
        for _ in range(n):
            pass
        return (time.perf_counter_ns() - t0) / n

    out = {"loop_ns": empty()}
    for state in ("off", "on"):
        if state == "on":
            spans.enable()
        for ids in (False, True):
            out[f"{state}_{'ids' if ids else 'no_ids'}_ns"] = loop(ids)
            spans.drain()
        t0 = time.perf_counter_ns()
        for _ in range(n):
            spans.stamp()
        out[f"{state}_stamp_ns"] = (time.perf_counter_ns() - t0) / n
    spans.disable()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    mode, args = sys.argv[1], sys.argv[2:]
    if mode == "fixture":
        sys.exit(fixture(args[0]))
    if mode == "slice":
        sys.exit(slice_(args[0]))
    if mode == "hunt":
        sys.exit(hunt(args[0], *(float(a) for a in args[1:])))
    if mode == "cost":
        sys.exit(cost())
    sys.exit(f"unknown mode {mode!r}")
