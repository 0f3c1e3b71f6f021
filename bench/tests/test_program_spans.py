"""The program's spans mapped onto the device trace: the five numbers read
from them and ``program_gaps`` on synthetic spans, the old chip trace read
exactly as before, and the clock check on a recording made on a TPU v5e
(``data/paper_band_spans.*``, written by ``record_spans.py fixture``)."""

import json
import pathlib
import types

import pytest

from bench import program_spans as ps_mod
from bench import trace_reduce as tr
from bench.readout import KERNEL_EVENT

DATA = pathlib.Path(__file__).parent / "data"
OLD = DATA / "paper_band_trace.xplane.pb.gz"
NEW = DATA / "paper_band_spans"
KERNEL = "%mandelbrot.1 = (s32[8,5632]) custom-call(f32[8,5632] %pad.0)"
START = 1_800_000_000_000_000_000  # a profile's start, epoch ns


def _profile():
    def plane(name, lines, stats=()):
        return types.SimpleNamespace(name=name, stats=list(stats), lines=[
            types.SimpleNamespace(name=ln, events=[
                types.SimpleNamespace(name=n, start_ns=s, end_ns=e)
                for n, s, e in evs]) for ln, evs in lines.items()])

    return types.SimpleNamespace(planes=[
        plane("/host:CPU", {"python": [("Execute", 0, 1000)]}),
        plane("/device:TPU:0", {tr.OPS_LINE: [(KERNEL, 100, 300),
                                              (KERNEL, 500, 700)]}),
        plane(ps_mod.TASK_PLANE, {}, [(ps_mod.START_STAT, START),
                                      ("profile_stop_time", START + 2000)]),
    ])


def _rec(name, start, end, span_id, parent=None, **ids):
    # As spans.drain() gives a record, on the epoch clock.
    return [name, START + start, START + end, span_id, parent, "t", ids]


# Two workers over the window (0, 1000): worker 0 runs item 0 from 0 to
# 400 then idles; worker 1 idles to 450 and runs item 1 from 450 to 800.
NODE = [
    _rec("node.idle", -50, 0, 1, worker=0),
    _rec("node.queue", -20, 0, 2, job=5, item=0),
    _rec("node.work", 0, 400, 3, job=5, item=0),
    _rec("band.call", 100, 300, 4, 3, job=5, item=0),
    _rec("node.hold", 400, 460, 5, job=5, item=0),
    _rec("node.idle", 400, 1100, 6, worker=0),
    _rec("node.idle", -10, 450, 7, worker=1),
    _rec("node.queue", 20, 450, 8, job=5, item=1),
    _rec("node.work", 450, 800, 9, job=5, item=1),
    _rec("band.call", 500, 700, 10, 9, job=5, item=1),
    _rec("node.hold", 800, 900, 11, job=5, item=1),
    _rec("node.idle", 800, 1200, 12, worker=1),
]
HOST = [
    _rec("service.admit", 10, 30, 1, job=5),
    _rec("service.admit", 950, 1030, 2, job=6),  # ends past the window
    _rec("host.collect", 440, 470, 3, job=5, n=1),
]


def _mapped(node_dropped=0, host_dropped=0):
    summary, start = ps_mod.read_profile(_profile())
    return summary, ps_mod.map_spans(
        {"node": (NODE, node_dropped), "host": (HOST, host_dropped)},
        start, summary.window)


def test_spans_map_onto_the_trace_by_its_start_and_stay_apart():
    summary, ps = _mapped()
    assert summary.window == (0, 1000)
    work = [s for s in ps.spans if s.name == "node.work"]
    assert [(s.start, s.end, s.ids) for s in work] == [
        (0.0, 400.0, {"job": 5, "item": 0}),
        (450.0, 800.0, {"job": 5, "item": 1})]
    # The trace's own numbers do not see the spans.
    assert [h.name for h in summary.host] == ["Execute"]
    assert summary.idle_gaps() == [["Execute", pytest.approx(600e-9)]]
    assert ps_mod.outside_ns(ps, summary.kernel_events(KERNEL_EVENT)) == [
        0.0, 0.0]
    assert len(ps.inside()) == len(NODE + HOST) - 6  # six cross an edge


@pytest.mark.parametrize("name,expected", [
    ("job_admit_ms.p50", 20e-6),  # one admit inside the window, 20 ns
    ("work_queue_wait_us.p50", 430e-3),  # item 1's; item 0's starts before
    ("worker_idle_share", 100.0 * (600 + 450 + 200) / 2000),
    ("band_work_us.p50", 375e-3),
    ("result_hold_us.p50", 80e-3),
])
def test_the_span_numbers(name, expected):
    _, ps = _mapped()
    read = ps_mod.METRICS[name]
    assert read(ps, 2) == pytest.approx(expected)
    # Any span dropped in either process, or none recorded: no number.
    for dropped in ({"node_dropped": 1}, {"host_dropped": 3}):
        assert read(_mapped(**dropped)[1], 2) is None
    assert read(ps_mod.ProgramSpans(window=(0, 1000)), 2) is None
    assert read(None, 2) is None


def test_program_gaps_label_idle_time_by_the_shortest_span():
    summary, ps = _mapped()
    # The idle gaps (0, 100), (300, 500) and (700, 1000): at 50 the
    # shortest span is item 0's node.work (item 1's queue and worker 1's
    # idle are longer); at 400 item 0's hold, which starts there; at 850
    # item 1's hold.
    assert ps_mod.program_gaps(summary, ps) == [
        ["node.hold", pytest.approx(500e-9)],
        ["node.work", pytest.approx(100e-9)]]
    bare = ps_mod.ProgramSpans(window=summary.window)
    assert ps_mod.program_gaps(summary, bare) == [
        [ps_mod.NO_PROGRAM_SPAN, pytest.approx(600e-9)]]


def test_the_self_time_table():
    _, ps = _mapped()
    table = {row[0]: row[1:] for row in ps_mod.self_time_table(ps)}
    assert table["node.work"] == (2, pytest.approx(750e-9),
                                  pytest.approx(350e-9))
    assert table["band.call"] == (2, pytest.approx(400e-9),
                                  pytest.approx(400e-9))


def test_the_old_chip_trace_reads_as_before_and_has_its_start():
    summary, start = ps_mod.read_file(str(OLD))
    assert start == 1792291665860456932
    before = tr.read_file(str(OLD))
    assert summary.window == before.window
    assert summary.busy_s == before.busy_s
    assert summary.idle_gaps() == before.idle_gaps()
    assert summary.device_ops() == before.device_ops()


def test_the_recorded_spans_share_the_chip_traces_clock():
    # 12 bands of 4 x 5600 points at escape 1000, TPU v5 lite, spans on in
    # host and node around the node's profiler.
    summary, start = ps_mod.read_file(f"{NEW}.xplane.pb.gz")
    recording = json.loads(pathlib.Path(f"{NEW}.json").read_text())
    ps = ps_mod.map_spans(recording["spans"], start, summary.window)
    assert ps.dropped == 0
    kernels = summary.kernel_events(KERNEL_EVENT)
    assert len(kernels) == recording["bands"] == 12
    # Every kernel event lies inside a node.work span, within 50 us.
    assert max(ps_mod.outside_ns(ps, kernels)) <= 50e3
    # Tighter, and causal: inside some band's stretch from its jitted call
    # to the end of its read-back, which waits for the kernel.
    ends = {(s.ids["job"], s.ids["item"]): s.end for s in ps.spans
            if s.name == "band.readback"}
    bands = ps_mod.ProgramSpans(window=ps.window, spans=[
        ps_mod.ProgramSpan("band", s.start, ends[(s.ids["job"],
                                                  s.ids["item"])],
                           "node", 0, None, "", {})
        for s in ps.spans if s.name == "band.call"])
    assert max(ps_mod.outside_ns(bands, kernels, "band")) <= 50e3
    assert len(bands.spans) == 12
