"""The reduction from a profiler trace to the per-layer metrics: its
interval arithmetic on synthetic events, the whole reduction on a small
trace recorded on a TPU v5e (``data/paper_band_trace.xplane.pb.gz``,
written by ``record_trace.py``), and one such file per node merged."""

import gzip
import pathlib
import statistics
import types

import pytest

from bench import trace_reduce as tr
from bench.readout import KERNEL_EVENT

KERNEL = ("%mandelbrot.1 = (s32[8,5632], s32[8,5632]) custom-call(f32[8,5632] "
          "%pad.0, f32[8,5632] %pad.2), custom_call_target=\"tpu_custom_call\"")
FIXTURE = pathlib.Path(__file__).parent / "data" / "paper_band_trace.xplane.pb.gz"


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    busy = tr.union([(1, 2), (4, 6)])
    assert tr.gaps_of(busy, (0, 10)) == [(0, 1), (2, 4), (6, 10)]
    assert tr.idle_within(busy, 1.5, 5) == pytest.approx(2.0)
    assert tr.idle_within(busy, 6, 9) == 3


def test_gaps_are_labelled_by_the_innermost_host_event():
    host = sorted([tr.Interval("outer", 0, 100), tr.Interval("inner", 10, 20),
                   tr.Interval("late", 60, 70)], key=lambda h: h.start)
    gaps = [(12, 16), (30, 40), (62, 64), (200, 210)]
    assert tr.label_gaps(gaps, host) == ["inner", "outer", "late",
                                         tr.NO_HOST_EVENT]


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, end_ns=e)
            for n, s, e in evs]) for ln, evs in lines.items()])


def test_a_synthetic_trace_reduces_to_busy_idle_and_kernel_gaps():
    profile = types.SimpleNamespace(planes=[
        _plane("/host:CPU", {"worker": [("dispatch", 0, 1000)]}),
        _plane("/device:TPU:0", {
            tr.OPS_LINE: [(KERNEL, 100, 300),
                          ("%reduce_sum.7 = s32[] reduce(%a)", 300, 350),
                          (KERNEL, 500, 700)],
            "XLA Modules": [("jit_mandelbrot", 100, 700)]}),
    ])
    summary = tr.summarise(profile)
    assert summary.window == (0, 1000)
    assert summary.busy_s == pytest.approx(450e-9)
    assert summary.idle_share == pytest.approx(0.55)
    assert len(summary.kernel_events(KERNEL_EVENT)) == 2
    assert summary.launch_gaps_ns(KERNEL_EVENT) == [150]
    assert summary.device_ops() == [["%mandelbrot.1", pytest.approx(400e-9)],
                                    ["%reduce_sum.7", pytest.approx(50e-9)]]
    assert summary.idle_gaps() == [["dispatch", pytest.approx(550e-9)]]


def test_a_trace_without_a_tpu_is_refused():
    profile = types.SimpleNamespace(planes=[
        _plane("/host:CPU", {"python": [("f", 0, 10)]})])
    with pytest.raises(tr.NoDeviceTrace):
        tr.summarise(profile)


def test_the_recorded_chip_trace_reduces_to_its_twelve_bands():
    # 12 bands of 4 x 5600 points at escape 1000, TPU v5 lite.
    summary = tr.read_file(str(FIXTURE))
    assert len(summary.chips) == 1
    kernels = summary.kernel_events(KERNEL_EVENT)
    assert len(kernels) == 12
    kernel_ns = sum(k.end - k.start for k in kernels)
    assert kernel_ns == 1590263.0  # 132.5 us a band
    assert len(summary.launch_gaps_ns(KERNEL_EVENT)) == 11
    assert 0 < summary.busy_s < summary.window_s
    assert summary.busy_s * 1e9 >= kernel_ns
    ops = summary.device_ops()
    assert ops[0] == ["%mandelbrot.1", pytest.approx(kernel_ns * 1e-9)]
    gaps = summary.idle_gaps()
    assert 1 <= len(gaps) <= 10
    idle = sum(s for _, s in summary.idle_gaps(top=10**6))
    assert idle == pytest.approx(summary.window_s - summary.busy_s)


TRACE = gzip.decompress(FIXTURE.read_bytes())


def _node_dirs(tmp_path, nodes):
    """The recorded trace as each node of ``nodes`` writes it."""
    for node in nodes:
        profile = tmp_path / node / "plugins" / "profile" / "1"
        profile.mkdir(parents=True)
        (profile / "host.xplane.pb").write_bytes(TRACE)
    return str(tmp_path)


def test_one_node_file_reads_as_the_file_itself(tmp_path):
    one = tr.read_file(str(FIXTURE))
    loaded = tr.load(_node_dirs(tmp_path, ["node0"]))
    assert len(loaded.chips) == 1
    assert (loaded.busy_s, loaded.window_s, loaded.idle_share) == (
        one.busy_s, one.window_s, one.idle_share)
    assert loaded.kernel_events(KERNEL_EVENT) == one.kernel_events(
        KERNEL_EVENT)
    assert loaded.launch_gaps_ns(KERNEL_EVENT) == one.launch_gaps_ns(
        KERNEL_EVENT)
    assert loaded.device_ops() == one.device_ops()
    assert loaded.idle_gaps() == one.idle_gaps()


def test_a_file_per_node_is_read_chip_by_chip(tmp_path):
    one = tr.read_file(str(FIXTURE))
    both = tr.load(_node_dirs(tmp_path, ["node0", "node1"]))
    assert len(both.chips) == 2
    assert both.idle_share == one.idle_share
    assert both.busy_s == pytest.approx(one.busy_s)
    assert both.window_s == one.window_s
    assert len(both.kernel_events(KERNEL_EVENT)) == 2 * len(
        one.kernel_events(KERNEL_EVENT))
    gaps = both.launch_gaps_ns(KERNEL_EVENT)
    assert len(gaps) == 2 * len(one.launch_gaps_ns(KERNEL_EVENT))
    assert statistics.median(gaps) == statistics.median(
        one.launch_gaps_ns(KERNEL_EVENT))
    idle = dict(both.idle_gaps(top=10**6))
    assert idle.keys() == dict(one.idle_gaps(top=10**6)).keys()
    for name, seconds in one.idle_gaps(top=10**6):
        assert idle[name] == pytest.approx(2 * seconds)
    assert [n for n, _ in both.device_ops()] == [
        n for n, _ in one.device_ops()]


def test_two_traces_of_one_node_are_refused(tmp_path):
    root = pathlib.Path(_node_dirs(tmp_path, ["node0"]))
    (root / "node0" / "again.xplane.pb").write_bytes(TRACE)
    with pytest.raises(tr.NoDeviceTrace, match="node0"):
        tr.load(str(root))
    with pytest.raises(tr.NoDeviceTrace):
        tr.load(str(tmp_path / "node0" / "empty"))
