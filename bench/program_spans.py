"""The program's own spans on the device trace's clock, and the numbers
read from them.

The host and the node-loader record spans with ``time.time_ns()``
(``repro.cluster.telemetry.spans``): epoch nanoseconds.  The profiler's
trace stamps its events relative to the start of the profile, and keeps
that start, in epoch nanoseconds too, as the ``profile_start_time`` stat of
its ``Task Environment`` plane.  So a span maps onto the trace's time base
by subtracting that start.

The mapped spans are kept in a list of their own, apart from the trace's
host events: they label no ``idle_gaps`` and do not widen the trace's
window, so every number that ``bench/trace_reduce.py`` reads from a trace
reads the same with or without them.  From them:

- five per-layer numbers, each over the spans that lie wholly inside the
  traced window, and ``None`` where a process dropped any span or none
  was recorded (:data:`METRICS`);
- ``program_gaps``: the device-idle time of the window, by the shortest
  program span that covers each gap's middle (:func:`program_gaps`);
- a table of each span name's count, total time and self time over the
  window (:func:`self_time_table`).
"""

from __future__ import annotations

import bisect
import gzip
import statistics
from dataclasses import dataclass, field
from typing import Callable

from bench import trace_reduce

TASK_PLANE = "Task Environment"
START_STAT = "profile_start_time"
NO_PROGRAM_SPAN = "(no program span)"
# The spans that :func:`outside_ns` weighs for an event: those that start
# last before it ends, enough for every worker's band around it.
NEAREST = 64


@dataclass
class ProgramSpan:
    name: str
    start: float  # ns on the trace's time base
    end: float
    proc: str  # "host" or "node"
    span_id: int
    parent_id: int | None
    thread: str
    ids: dict

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclass
class ProgramSpans:
    """Every process's spans of one traced run, on the trace's time base."""

    window: tuple[float, float]  # the trace's window, ns
    spans: list[ProgramSpan] = field(default_factory=list)  # by start
    dropped: int = 0  # summed over the processes

    def inside(self, name: str | None = None) -> list[ProgramSpan]:
        """The spans (named ``name``) that lie wholly inside the window."""
        lo, hi = self.window
        return [s for s in self.spans if lo <= s.start and s.end <= hi
                and (name is None or s.name == name)]

    def readable(self) -> bool:
        """True where a number read from these spans can be trusted: some
        were recorded and no process dropped any."""
        return bool(self.spans) and self.dropped == 0


def profile_start_ns(profile) -> int:
    """The profile's start in epoch nanoseconds, from a
    ``jax.profiler.ProfileData``."""
    for plane in profile.planes:
        if plane.name == TASK_PLANE:
            for name, value in plane.stats:
                if name == START_STAT:
                    return int(value)
    raise trace_reduce.NoDeviceTrace(
        f"the trace has no {START_STAT} in its {TASK_PLANE!r} plane")


def read_file(path: str):
    """One ``.xplane.pb`` file (gzipped where it ends in ``.gz``): its
    :class:`~bench.trace_reduce.TraceSummary` and its start in epoch ns."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return read_profile(ProfileData.from_serialized_xspace(raw))


def read_profile(profile):
    """A ``jax.profiler.ProfileData``'s summary and its start in epoch ns."""
    return trace_reduce.summarise(profile), profile_start_ns(profile)


def map_spans(drained: dict[str, tuple[list, int]], start_ns: int,
              window: tuple[float, float]) -> ProgramSpans:
    """``drained`` maps each process ("host", "node") to what
    ``spans.drain()`` returned there: its records and drop count."""
    out = ProgramSpans(window=window)
    for proc, (records, dropped) in drained.items():
        out.dropped += int(dropped)
        for name, start, end, span_id, parent_id, thread, ids in records:
            out.spans.append(ProgramSpan(
                name, float(start - start_ns), float(end - start_ns), proc,
                span_id, parent_id, thread, dict(ids)))
    out.spans.sort(key=lambda s: s.start)
    return out


def _median(ps: ProgramSpans | None, name: str, scale: float
            ) -> float | None:
    if ps is None or not ps.readable():
        return None
    durations = [s.duration for s in ps.inside(name)]
    return statistics.median(durations) / scale if durations else None


def job_admit_ms_p50(ps, workers=None):
    """Median ``service.admit``: a job's submit to its first WORK_BATCH."""
    return _median(ps, "service.admit", 1e6)


def work_queue_wait_us_p50(ps, workers=None):
    """Median ``node.queue``: a band decoded to a worker taking it."""
    return _median(ps, "node.queue", 1e3)


def band_work_us_p50(ps, workers=None):
    """Median ``node.work``: the worker's call of the work function."""
    return _median(ps, "node.work", 1e3)


def result_hold_us_p50(ps, workers=None):
    """Median ``node.hold``: a result buffered to its RESULT_BATCH sent."""
    return _median(ps, "node.hold", 1e3)


def worker_idle_share(ps, workers):
    """``node.idle`` time inside the window over workers x window, in %."""
    if ps is None or not ps.readable() or not workers:
        return None
    lo, hi = ps.window
    idle = sum(max(0.0, min(s.end, hi) - max(s.start, lo))
               for s in ps.spans if s.name == "node.idle")
    return 100.0 * idle / (workers * (hi - lo))


# Metric name -> reader of (spans, node workers): what a per-layer metric
# of the benchmark reads from a traced run's program spans.
METRICS: dict[str, Callable] = {
    "job_admit_ms.p50": job_admit_ms_p50,
    "work_queue_wait_us.p50": work_queue_wait_us_p50,
    "worker_idle_share": worker_idle_share,
    "band_work_us.p50": band_work_us_p50,
    "result_hold_us.p50": result_hold_us_p50,
}


def program_gaps(summary, ps: ProgramSpans, top: int = 10) -> list[list]:
    """The window's device-idle time by the shortest program span that
    covers each gap's middle (``NO_PROGRAM_SPAN`` where none does), in
    seconds summed over the chips' gaps."""
    intervals = [trace_reduce.Interval(s.name, s.start, s.end)
                 for s in ps.spans]
    total: dict[str, float] = {}
    for chip in summary.chips:
        gaps = trace_reduce.gaps_of(chip.busy, chip.window)
        for (s, e), label in zip(gaps, trace_reduce.label_gaps(gaps,
                                                               intervals)):
            if label == trace_reduce.NO_HOST_EVENT:
                label = NO_PROGRAM_SPAN
            total[label] = total.get(label, 0.0) + e - s
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9] for name, ns in ranked]


def outside_ns(ps: ProgramSpans, events, name: str = "node.work"
               ) -> list[float]:
    """For each trace event, how far it lies outside the mapped span
    ``name`` that holds it best (0 where one covers it): the check that the
    spans and the trace share a clock."""
    spans = sorted((s.start, s.end) for s in ps.spans if s.name == name)
    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev.end)
        out.append(min((max(0.0, s - ev.start, ev.end - e)
                        for s, e in spans[max(0, i - NEAREST):i + 1]),
                       default=float("inf")))
    return out


def self_time_table(ps: ProgramSpans) -> list[tuple[str, int, float, float]]:
    """``(name, count, total_s, self_s)`` per span name over the spans
    inside the window, by total time; a span's children are found in its
    own process."""
    from repro.cluster.telemetry.spans import self_times

    table: dict[str, list] = {}
    for proc in sorted({s.proc for s in ps.spans}):
        records = [(s.name, s.start, s.end, s.span_id, s.parent_id,
                    s.thread, s.ids) for s in ps.inside() if s.proc == proc]
        for name, (n, total, own) in self_times(records).items():
            acc = table.setdefault(name, [0, 0.0, 0.0])
            acc[0] += n
            acc[1] += total * 1e-9
            acc[2] += own * 1e-9
    return sorted(((name, *v) for name, v in table.items()),
                  key=lambda row: -row[2])
