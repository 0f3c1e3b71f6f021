"""Drive one cell's traffic through a warm ``ClusterService`` and record it.

The pool is the program's own: ``ClusterService(nodes=, workers=,
preload=("repro.kernels.mandelbrot.ops",))`` with its default launcher and
liveness, ``nodes`` and ``workers`` from the configuration's file.  Each
node-loader owns the chips it finds: one node on one chip, or, in a cell
of several nodes, one node per chip.  Every timed job is the program's
``farm_spec`` over the configuration's plane, read back through
``JobHandle.result()``.  This process never touches JAX while the pool is
up: the chips belong to the nodes.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

from bench import probes, traffic_gen
from bench.cell import Cell

PRELOAD = ("repro.kernels.mandelbrot.ops",)
# The job running at the window's close gets this long past it to finish;
# one that has not by then never came.
GRACE_S = 60.0
JOB_TIMEOUT_S = 600.0
PROBE_TIMEOUT_S = 300.0  # the first probe waits for the nodes' boot
# A traced run traces this slice of its window, from TRACE_LEAD_S in.
TRACE_S = 4.0
TRACE_LEAD_S = 1.0


class NoAccelerator(RuntimeError):
    """A node finds no device of the platform, or the pool too few."""


@dataclass
class JobRecord:
    """One timed job, on this process's monotonic clock."""

    lines: int
    bands: int
    due: float
    handle: Any = None
    done_at: float | None = None
    result: tuple | None = None
    error: str | None = None
    bands_at_close: int | None = None
    # The program's own view of the job, kept to explain a slow one.
    first_result_ms: float | None = None
    code_shipped: int | None = None
    duplicates: int | None = None
    items_by_node: dict[str, int] | None = None

    def collected(self) -> int:
        """Bands collected so far (safe to read from another thread)."""
        handle = self.handle
        if self.result is not None:
            return self.bands
        return 0 if handle is None else handle.stats()["items_collected"]

    def finish(self) -> None:
        """Keep the answer of the finished job, or its error, and let its
        handle go."""
        handle = self.handle
        stats = handle.stats()
        self.first_result_ms = stats["submit_to_first_result_ms"]
        self.code_shipped = stats["code_shipped"]
        self.duplicates = stats["duplicates_dropped"]
        self.items_by_node = {nid: n.get("items", 0)
                              for nid, n in stats["nodes"].items()}
        if handle.error is None:
            self.result = tuple(handle.result())
        else:
            self.error = f"{type(handle.error).__name__}: {handle.error}"
        self.handle = None

    @property
    def latency_s(self) -> float | None:
        """Due time to last result, as this client saw it."""
        return None if self.done_at is None else self.done_at - self.due


@dataclass
class Recording:
    """What one run of a cell saw, before it is judged and reduced."""

    device: dict
    setup_s: float
    window_start: float
    window_end: float
    jobs: list[JobRecord]
    points_per_band: int
    memory_peak_bytes: int | None  # of the fullest device
    gc_pauses: dict | None = None  # per process: (end, generation, seconds)
    trace_dir: str | None = None
    wire: dict | None = None  # over the traced slice: bytes and bands

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def window_bands(self) -> int:
        """Bands collected inside the window, the unfinished jobs' too."""
        total = 0
        for job in self.jobs:
            if job.done_at is not None and job.done_at <= self.window_end \
                    and job.result is not None:
                total += job.bands
            elif job.bands_at_close is not None:
                total += job.bands_at_close
        return total


def job_spec(config: dict, lines: int):
    """The program's farm over ``lines`` lines of the configuration."""
    from repro.apps.mandelbrot_farm import farm_spec

    return farm_spec(1, config["workers"], lines=lines,
                     width=config["width"], max_iters=config["max_iters"],
                     interpret=config["interpret"])


def every_node(svc, work: Callable[[Any], Any], args: dict[str, Any], *,
               priority: int = 0) -> dict[str, Any]:
    """``work(args[node])`` run once in each node named in ``args``, all at
    about one moment (``probes.every_node_spec``): node id -> its answer."""
    meet_dir = tempfile.mkdtemp(prefix="bench-meet-")
    try:
        spec = probes.every_node_spec(work, args, svc.workers, meet_dir)
        return dict(svc.submit(spec, priority=priority,
                               timeout=PROBE_TIMEOUT_S).result())
    finally:
        shutil.rmtree(meet_dir, ignore_errors=True)


def _wire_bytes(svc) -> int:
    """Bytes both ways so far on every node's connection, from the pool's
    metrics snapshot."""
    nodes = svc.metrics_snapshot()["nodes"].values()
    return sum(n["wire"]["bytes_sent"] + n["wire"]["bytes_recv"]
               for n in nodes if "wire" in n)


class _Tracer:
    """Starts and stops every node's profiler over a slice of the window,
    with control jobs ahead of the traffic, each node writing under
    ``<dir>/<node id>/``, and counts the wire bytes of every node and the
    bands of that slice: from the end of the start, when every node
    traces, to the stop's submit, before any node stops."""

    def __init__(self, svc, nodes: list[str], jobs: list[JobRecord],
                 start: float, stop: float):
        self.svc = svc
        self.nodes = nodes
        self.jobs = jobs
        self.start, self.stop = start, stop
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.wire: dict | None = None
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._run, name="bench-trace",
                                       daemon=True)

    def _bands(self) -> int:
        return sum(j.collected() for j in list(self.jobs))

    def _run(self) -> None:
        try:
            time.sleep(max(0.0, self.start - time.monotonic()))
            every_node(self.svc, probes.start_trace,
                       {nid: os.path.join(self.dir, nid)
                        for nid in self.nodes}, priority=1)
            bytes0, bands0 = _wire_bytes(self.svc), self._bands()
            time.sleep(max(0.0, self.stop - time.monotonic()))
            bytes1, bands1 = _wire_bytes(self.svc), self._bands()
            every_node(self.svc, probes.stop_trace,
                       dict.fromkeys(self.nodes, 0), priority=1)
            self.wire = {"bytes": bytes1 - bytes0, "bands": bands1 - bands0}
        except BaseException as exc:  # reported by join()
            self.error = exc

    def join(self) -> None:
        self.thread.join()
        if self.error is not None:
            raise self.error


def closed_loop(svc, spec, lines: int, bands: int, end: float,
                jobs: list[JobRecord]) -> None:
    """One client submits ``spec``, waits for its last band, keeps the
    answer, and submits it again, until ``end``.  The job still running
    then is left in ``jobs`` with its handle."""
    while time.monotonic() < end:
        job = JobRecord(lines=lines, bands=bands, due=time.monotonic())
        job.handle = svc.submit(spec, timeout=JOB_TIMEOUT_S)
        jobs.append(job)
        if job.handle.wait(max(0.0, end - time.monotonic())):
            job.done_at = time.monotonic()
            job.finish()


def pool_devices(reports: dict[str, dict], chips: int, *,
                 platform: str | None, device_kinds: dict | None) -> dict:
    """The pool's devices from each node's ``probes.device_report``, or
    the refusal of a run without the chips the cell needs: a node on
    another platform, two nodes in one process, a node of several that
    does not own exactly one chip, fewer devices than ``chips``, or a
    device kind that the benchmark's table lacks."""
    for nid, r in reports.items():
        if platform is not None and r["platform"] != platform:
            raise NoAccelerator(f"{nid} runs on {r['platform']!r}: no "
                                f"{platform} accelerator")
    pids = [r["pid"] for r in reports.values()]
    if len(set(pids)) < len(pids):
        raise NoAccelerator(f"two nodes answered from one process: {pids}")
    if len(reports) > 1:
        for nid, r in reports.items():
            if r["count"] != 1:
                raise NoAccelerator(f"{nid} owns {r['count']} devices; in "
                                    "a pool of several nodes each owns one")
    count = sum(r["count"] for r in reports.values())
    if count < chips:
        raise NoAccelerator(f"{count} device(s), the cell asks for {chips}")
    kinds = sorted({r["kind"] for r in reports.values()})
    for kind in kinds:
        if device_kinds is not None and kind not in device_kinds:
            raise KeyError(f"device kind {kind!r} is not in the "
                           "benchmark's device table")
    if len(kinds) > 1:
        raise NoAccelerator(f"the nodes' devices are of kinds {kinds}")
    first = next(iter(reports.values()))
    return {"platform": first["platform"], "kind": first["kind"],
            "count": count, "pids": pids}


def first_band(spec) -> tuple[Callable[[Any], Any], Any]:
    """``spec``'s work function and its first item."""
    pipeline = spec.as_pipeline()
    details = pipeline.emit.e_details
    item, _ = details.create(details.initial_state())
    return pipeline.stages[0].node_net.group.function, item


def boot(svc, cell: Cell, warm, *, platform: str | None,
         device_kinds: dict | None, log: Callable[[str], None]) -> dict:
    """Ask every node for its devices, refuse a run without the chips the
    cell needs, watch the collector's pauses here and in every node, run
    ``warm``'s first band once in every node, which ships the code and
    compiles the band program there wherever the warm jobs' bands land,
    then run ``warm`` twice."""
    nodes = svc.pool_alive()
    reports = every_node(svc, probes.device_report, dict.fromkeys(nodes, 0))
    log(f"node devices: {reports}")
    device = pool_devices(reports, cell.chips, platform=platform,
                          device_kinds=device_kinds)
    every_node(svc, probes.watch_gc, dict.fromkeys(nodes, 0))
    probes.watch_gc(0)
    work, item = first_band(warm)
    t0 = time.monotonic()
    every_node(svc, work, dict.fromkeys(nodes, item))
    log(f"first band on every node: {time.monotonic() - t0} s")
    seconds = []
    for _ in range(2):
        t0 = time.monotonic()
        svc.submit(warm, timeout=JOB_TIMEOUT_S).result()
        seconds.append(time.monotonic() - t0)
    log(f"warm jobs: {seconds} s (the first ships the code and compiles, "
        "or loads the compiled band program from the cache)")
    # Where set-up goes: the pool's start to its nodes' registration, and
    # each node's boot with, inside it, its backend start.
    nodes = svc.metrics_snapshot().get("nodes", {})
    log(f"pool boot_ms={svc.boot_ms} nodes=" + str({
        nid: {k: (n.get("report") or {}).get(k)
              for k in ("boot_ms", "backend_ms")}
        for nid, n in nodes.items()}))
    return device


def record(cell: Cell, *, seconds: float, trace: bool, t_start: float,
           platform: str | None = "tpu", device_kinds: dict | None = None,
           spec_factory: Callable[[dict, int], Any] | None = None,
           log: Callable[[str], None] = print) -> Recording:
    """Boot the pool, warm it at the cell's band shape, run the window,
    wait for the job still running at its close, read the fullest device's
    memory peak, close the pool.  ``platform=None`` skips the look for an
    accelerator (the CPU rehearsal does); ``spec_factory`` builds the jobs
    in place of the program's farm (the control does)."""
    from repro.cluster.service import ClusterService

    spec_factory = spec_factory or job_spec
    config, traffic = cell.config, cell.traffic
    lpb = config["lines_per_band"]
    workers = config["workers"]
    lines = traffic_gen.job_lines(traffic, config)
    bands = lines // lpb
    spec = spec_factory(config, lines)
    warm = spec_factory(config, workers * lpb)  # one band per worker

    svc = ClusterService(nodes=config["nodes"], workers=workers,
                         preload=PRELOAD)
    jobs: list[JobRecord] = []
    tracer = None
    try:
        with svc:
            device = boot(svc, cell, warm, platform=platform,
                          device_kinds=device_kinds, log=log)
            nodes = svc.pool_alive()
            start = time.monotonic()
            setup_s = start - t_start
            end = start + seconds
            log(f"setup_s={setup_s} window opens")
            if trace:
                lead = min(TRACE_LEAD_S, seconds / 4)
                tracer = _Tracer(svc, nodes, jobs, start + lead,
                                 start + lead + min(TRACE_S, seconds / 2))
                tracer.thread.start()
            closed_loop(svc, spec, lines, bands, end, jobs)
            running = [j for j in jobs if j.handle is not None]
            for job in running:
                job.bands_at_close = job.handle.stats()["items_collected"]
            if tracer is not None:
                tracer.join()
            for job in running:
                if job.handle.wait(max(0.0, end + GRACE_S - time.monotonic())):
                    job.done_at = time.monotonic()
                    job.finish()
                else:
                    job.error = "no result within the grace"
                    job.handle = None
            peaks = every_node(svc, probes.memory_peak,
                               dict.fromkeys(nodes, 0))
            peak = max((p for p in peaks.values() if p is not None),
                       default=None)
            gc_pauses = {"host": probes.gc_pauses(0),
                         **every_node(svc, probes.gc_pauses,
                                      dict.fromkeys(nodes, 0))}
    except BaseException:
        for nid, h in svc.handles.items():
            for line in h.logs()[-40:]:
                log(f"[{nid}] {line}")
        if tracer is not None:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        raise
    orphaned = svc.orphaned()
    if orphaned:
        raise RuntimeError(f"node-loaders still running: {orphaned}")
    return Recording(
        device=device, setup_s=setup_s, window_start=start, window_end=end,
        jobs=jobs, points_per_band=lpb * config["width"],
        memory_peak_bytes=peak, gc_pauses=gc_pauses,
        trace_dir=None if tracer is None else tracer.dir,
        wire=None if tracer is None else tracer.wire,
    )
