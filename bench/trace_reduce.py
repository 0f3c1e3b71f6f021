"""Reduce the profiler traces of the nodes to the numbers the metrics read.

Each node-loader writes one ``.xplane.pb`` per traced slice, under a
directory of its own, for the chips it owns: one node on one chip, or one
node per chip in a cell of several.  A file's device planes
(``/device:TPU:<n>``) carry, on their ``XLA Ops`` line, one event per
device operation; its host plane (``/host:CPU``) carries one line per host
thread with the node's runtime events (dispatch, transfers, waits).  Each
chip is read against its own file's window and host events:

- busy time: the union of the device operations' intervals, per chip;
- idle gaps: the rest of the chip's traced window, each labelled by what
  its node's host was doing at its middle (the shortest host event that
  covers it);
- kernel events: the device operations whose name matches the kernel's
  pattern, and the device-idle time between consecutive ones.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import heapq
import os
import re
import statistics
from collections import Counter
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
NO_HOST_EVENT = "(no host event)"


class NoDeviceTrace(RuntimeError):
    """The trace holds no TPU device plane, or no operation on one."""


@dataclass
class Interval:
    name: str
    start: float  # ns
    end: float


@dataclass
class ChipTrace:
    """One chip's device operations, with the window and host events of
    the trace file that holds it: its node's."""

    ops: list[Interval]
    busy: list[tuple[float, float]]
    window: tuple[float, float]  # ns
    host: list[Interval]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds with an operation on the chip."""
        return sum(e - s for s, e in self.busy) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


@dataclass
class TraceSummary:
    """The traced chips of every node, each read against its own file's
    window and host events; ``window`` and ``host`` span every file."""

    window: tuple[float, float]  # ns
    chips: list[ChipTrace]
    host: list[Interval]

    @property
    def window_s(self) -> float:
        """The chips' traced windows, averaged."""
        return statistics.fmean(chip.window_s for chip in self.chips)

    @property
    def busy_s(self) -> float:
        """Seconds with an operation on the device, averaged over chips."""
        return statistics.fmean(chip.busy_s for chip in self.chips)

    @property
    def idle_share(self) -> float:
        """Each chip's idle share over its own window, averaged."""
        return statistics.fmean(chip.idle_share for chip in self.chips)

    def kernel_events(self, pattern: str) -> list[Interval]:
        rx = re.compile(pattern)
        return [op for chip in self.chips for op in chip.ops
                if rx.search(op.name)]

    def launch_gaps_ns(self, pattern: str) -> list[float]:
        """Per chip, the device-idle time between each kernel event and
        the next one."""
        rx = re.compile(pattern)
        gaps = []
        for chip in self.chips:
            kernels = sorted((op for op in chip.ops if rx.search(op.name)),
                             key=lambda op: op.start)
            for a, b in zip(kernels, kernels[1:]):
                if b.start > a.end:
                    gaps.append(idle_within(chip.busy, a.end, b.start))
        return gaps

    def device_ops(self, top: int = 10) -> list[list]:
        """The device operations that took most time, by their HLO name
        (``%mandelbrot.1``), in seconds summed over the chips."""
        total: Counter = Counter()
        for chip in self.chips:
            for op in chip.ops:
                total[op.name.split(" = ")[0]] += op.end - op.start
        return [[name, ns * 1e-9] for name, ns in total.most_common(top)]

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Idle time by what the chip's node was doing meanwhile, in
        seconds summed over the chips' gaps."""
        total: Counter = Counter()
        for chip in self.chips:
            gaps = gaps_of(chip.busy, chip.window)
            for (s, e), label in zip(gaps, label_gaps(gaps, chip.host)):
                total[label] += e - s
        return [[name, ns * 1e-9] for name, ns in total.most_common(top)]


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps_of(busy, window) -> list[tuple[float, float]]:
    """The stretches of ``window`` that ``busy`` (merged, sorted) leaves."""
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, window[1])))
        t = max(t, e)
    if t < window[1]:
        gaps.append((t, window[1]))
    return [(s, e) for s, e in gaps if e > s]


def idle_within(busy, start: float, end: float) -> float:
    """Time in ``[start, end]`` that ``busy`` (merged, sorted) leaves."""
    covered = 0.0
    i = max(0, bisect.bisect_right([s for s, _ in busy], start) - 1)
    for s, e in busy[i:]:
        if s >= end:
            break
        covered += max(0.0, min(e, end) - max(s, start))
    return (end - start) - covered


def label_gaps(gaps, host: list[Interval]) -> list[str]:
    """For each gap, the name of the shortest host event that covers its
    middle, or ``NO_HOST_EVENT``.  ``host`` is sorted by start."""
    labels = []
    active: list[tuple[float, int]] = []  # (end, index) heap
    i = 0
    for s, e in gaps:
        mid = (s + e) / 2
        while i < len(host) and host[i].start <= mid:
            heapq.heappush(active, (host[i].end, i))
            i += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        covering = [host[j] for end, j in active if end >= mid]
        labels.append(min(covering, key=lambda h: h.end - h.start).name
                      if covering else NO_HOST_EVENT)
    return labels


def _events(line) -> list[Interval]:
    return [Interval(ev.name, float(ev.start_ns), float(ev.end_ns))
            for ev in line.events]


def summarise(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` of one node."""
    devices, host, spans = [], [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [ev for line in plane.lines if line.name == OPS_LINE
                   for ev in _events(line)]
            if ops:
                devices.append(sorted(ops, key=lambda o: o.start))
                spans += [min(o.start for o in ops), max(o.end for o in ops)]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += _events(line)
    if not devices:
        raise NoDeviceTrace("the trace has no operation on a TPU device")
    host.sort(key=lambda h: h.start)
    if host:
        spans += [host[0].start, max(h.end for h in host)]
    window = (min(spans), max(spans))
    chips = [ChipTrace(ops, union((o.start, o.end) for o in ops), window,
                       host) for ops in devices]
    return TraceSummary(window, chips, host)


def read_file(path: str) -> TraceSummary:
    """One ``.xplane.pb`` file (gzipped where it ends in ``.gz``), reduced."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return summarise(ProfileData.from_serialized_xspace(raw))


def load(trace_dir: str) -> TraceSummary:
    """Every node's trace under ``trace_dir``, reduced: one ``.xplane.pb``
    under each directory that ``trace_dir`` holds, one a node."""
    by_node: dict[str, list[str]] = {}
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        node = os.path.relpath(path, trace_dir).split(os.sep)[0]
        by_node.setdefault(node, []).append(path)
    if not by_node:
        raise NoDeviceTrace(f"no trace under {trace_dir}")
    for node, paths in by_node.items():
        if len(paths) != 1:
            raise NoDeviceTrace(f"expected one trace under {node}, found "
                                f"{len(paths)}")
    files = [read_file(by_node[node][0]) for node in sorted(by_node)]
    return TraceSummary(
        (min(f.window[0] for f in files), max(f.window[1] for f in files)),
        [chip for f in files for chip in f.chips],
        sorted((h for f in files for h in f.host), key=lambda h: h.start))
