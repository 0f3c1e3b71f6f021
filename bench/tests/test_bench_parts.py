"""The benchmark's parts on their own: the traffic reader, the judge,
the reference against the program's own oracle, the metric readers, and
the refusal of a pool whose devices the cell cannot use: a device kind
that the device table lacks, two nodes in one process, a node of several
that owns more than one chip."""

import gc
import json
import time
import types

import numpy as np
import pytest

from bench import drive, judge, readout, reference, traffic_gen
from bench.cell import load_cell
from bench.tests import rig

PLANE = {"min_x": -2.5, "min_y": 1.0, "range_x": 3.5}


@pytest.mark.parametrize("mix", [
    {"loop": "sideways", "job_lines": 4},
    {"loop": "closed"},
    {"loop": "open", "job_lines": 4, "rate_per_s": 50},
])
def test_a_malformed_mix_is_refused(mix):
    with pytest.raises(ValueError):
        traffic_gen.validate(mix)


def test_the_reference_agrees_with_the_programs_oracle_on_cpu():
    from repro.apps.mandelbrot_farm import farm_grid, farm_totals
    from repro.kernels.mandelbrot.ref import mandelbrot_reference

    x0, y0 = farm_grid(24, 384)
    iters, colour = mandelbrot_reference(x0, y0, 80)
    expected = farm_totals(np.asarray(iters), np.asarray(colour))
    got = reference.farm_answer(lines=24, width=384, max_iters=80,
                                escape_radius=2.0, plane=PLANE,
                                lines_per_band=4)
    assert got == expected
    x, y = reference.plane_axes(8, 4, 384, PLANE)
    assert np.array_equal(np.broadcast_to(x, (4, 384)), x0[8:12])
    assert np.array_equal(np.broadcast_to(y[:, None], (4, 384)), y0[8:12])


def test_the_control_precision_changes_the_answer():
    kw = dict(lines=16, width=256, max_iters=60, escape_radius=2.0,
              plane=PLANE, lines_per_band=4)
    assert (reference.farm_answer(**kw)
            != reference.farm_answer(dtype="bfloat16", **kw))


def _job(lines, result, bands=None):
    job = drive.JobRecord(lines=lines, bands=bands or lines // 4, due=0.0)
    job.result = result
    return job


def test_the_judge_holds_every_number_to_its_limit():
    config = {"lines_per_band": 4, "width": 10}

    def answer(lines):
        return (100 * lines, lines, 10 * lines)

    good = [_job(8, (800, 8, 80)), _job(8, (800, 8, 80))]
    verdict = judge.judge(good, config, answer)
    assert verdict["correct"] and judge.failed_jobs(good, config) == 0
    bad = good + [_job(8, (790, 9, 40)), _job(8, None)]
    verdict = judge.judge(bad, config, answer)
    assert not verdict["correct"]
    assert {k: c["value"] for k, c in verdict["checks"].items()} == {
        "missing_jobs": 1, "band_gap": 1, "iteration_gap": 10,
        "white_gap": 1}
    assert judge.failed_jobs(bad, config) == 2


def _view(jobs, *, window=(0.0, 2.0), trace=None, wire=None):
    rec = drive.Recording(device={}, setup_s=12.5, window_start=window[0],
                          window_end=window[1], jobs=jobs,
                          points_per_band=40, memory_peak_bytes=None,
                          wire=wire)
    return readout.RunView(cell=None, rec=rec, trace=trace)


def _readers(part):
    cell = load_cell(rig.REPO, "mandelbrot_paper.batch")
    return {m.name: m.reader for m in getattr(cell, part)}


def test_the_end_to_end_readers():
    done = _job(8, (1, 1, 80))
    done.due, done.done_at = 0.1, 0.6
    running = _job(8, (1, 1, 80))  # ends past the window's close
    running.due, running.done_at, running.bands_at_close = 1.5, 2.5, 1
    view = _view([done, running])
    readers = _readers("end_to_end")
    # 2 + 1 bands of 40 points in 2 s.
    assert readers["mpoints_per_s"].read(view) == pytest.approx(60e-6)
    assert readers["setup_s"].read(view) == 12.5


def test_the_per_layer_readers_without_a_trace_read_nothing():
    readers = _readers("per_layer")
    job = _job(8, (1, 1, 80))
    view = _view([job])
    for name, reader in readers.items():
        assert reader.read(view) is None, name
    wired = _view([], wire={"bytes": 5000, "bands": 20})
    assert readers["wire_bytes_per_band"].read(wired) == 250.0


class _FakeJob:
    """A job handle that is done at once, or at the wait's timeout not."""

    def __init__(self, done, error=None):
        self.is_done, self.error = done, error

    def wait(self, timeout):
        if not self.is_done:
            time.sleep(timeout)
        return self.is_done

    def result(self):
        return (1, 1, 80)

    def stats(self):
        return {"items_collected": 1, "submit_to_first_result_ms": 2.5,
                "code_shipped": 0, "duplicates_dropped": 0,
                "nodes": {"node0": {"items": 1}}}


class _FakeService:
    def __init__(self, finished, error_at=None):
        self.finished, self.error_at, self.jobs = finished, error_at, 0

    def submit(self, spec, **_kw):
        self.jobs += 1
        error = RuntimeError("lost") if self.jobs == self.error_at else None
        return _FakeJob(self.jobs <= self.finished, error)


def test_the_closed_loop_keeps_each_answer_and_leaves_the_running_job():
    jobs = []
    drive.closed_loop(_FakeService(3, error_at=2), None, 8, 2,
                      time.monotonic() + 0.2, jobs)
    assert [j.result for j in jobs] == [(1, 1, 80), None, (1, 1, 80), None]
    assert jobs[1].error == "RuntimeError: lost"
    assert [j.handle is None for j in jobs] == [True, True, True, False]
    assert [j.collected() for j in jobs] == [2, 0, 2, 1]
    assert jobs[0].done_at is not None and jobs[3].done_at is None
    assert jobs[0].first_result_ms == 2.5 and jobs[3].first_result_ms is None
    assert jobs[0].items_by_node == {"node0": 1}


class _FakeHandle:
    def __init__(self, value):
        self.value = value

    def result(self):
        return self.value


class _FakePool:
    """A pool of one node that answers the device probe with ``report``."""

    workers = 1

    def __init__(self, report):
        self.report = report
        self.jobs = 0

    def pool_alive(self):
        return ["node0"]

    def submit(self, spec, **_kw):
        self.jobs += 1
        return _FakeHandle([("node0", self.report)])


def test_a_device_kind_missing_from_the_table_is_an_error():
    kinds = json.loads((rig.BENCH / "devices.json").read_text())
    assert "TPU v5 lite" in kinds
    cell = types.SimpleNamespace(chips=1)
    pool = _FakePool({"platform": "tpu", "kind": "TPU v99", "count": 1,
                      "pid": 7})
    with pytest.raises(KeyError, match="TPU v99"):
        drive.boot(pool, cell, None, platform="tpu", device_kinds=kinds,
                   log=lambda _m: None)
    assert pool.jobs == 1  # refused before any warm-up
    for report in ({"platform": "cpu", "kind": "cpu", "count": 1, "pid": 7},
                   {"platform": "tpu", "kind": "TPU v5 lite", "count": 0,
                    "pid": 7}):
        with pytest.raises(drive.NoAccelerator):
            drive.boot(_FakePool(report), cell, None, platform="tpu",
                       device_kinds=kinds, log=lambda _m: None)


def test_the_gc_watch_keeps_long_collections_only(monkeypatch):
    from bench import probes

    monkeypatch.setitem(probes._gc, "pauses", [])
    monkeypatch.setattr(probes, "GC_PAUSE_S", 0.0)
    probes.watch_gc(0)
    probes.watch_gc(0)  # once is enough
    try:
        assert probes.gc.callbacks.count(probes._on_gc) == 1
        before = time.monotonic()
        gc.collect()
        pauses = probes.gc_pauses(0)
        assert pauses and all(end >= before and gen == 2 and took >= 0
                              for end, gen, took in pauses)
        monkeypatch.setattr(probes, "GC_PAUSE_S", 60.0)
        gc.collect()
        assert probes.gc_pauses(0) == pauses
    finally:
        probes.gc.callbacks.remove(probes._on_gc)


class _TwoNodes(_FakePool):
    def pool_alive(self):
        return ["node0", "node1"]


@pytest.mark.parametrize("reports, error", [
    ({"node0": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "pid": 7},
      "node1": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "pid": 7}}, "one process"),
    ({"node0": {"platform": "tpu", "kind": "TPU v5 lite", "count": 2,
                "pid": 7},
      "node1": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "pid": 8}}, "node0 owns 2 devices"),
    ({"node0": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "pid": 7},
      "node1": {"platform": "cpu", "kind": "cpu", "count": 1,
                "pid": 8}}, "node1 runs on 'cpu'"),
])
def test_a_pool_of_two_nodes_is_refused_unless_each_owns_one_chip(
        monkeypatch, reports, error):
    kinds = json.loads((rig.BENCH / "devices.json").read_text())
    asked = []

    def every_node(svc, work, args, **_kw):
        asked.append((work, sorted(args)))
        return reports

    monkeypatch.setattr(drive, "every_node", every_node)
    pool = _TwoNodes(None)
    with pytest.raises(drive.NoAccelerator, match=error):
        drive.boot(pool, types.SimpleNamespace(chips=2), None,
                   platform="tpu", device_kinds=kinds, log=lambda _m: None)
    from bench import probes

    assert asked == [(probes.device_report, ["node0", "node1"])]
    assert pool.jobs == 0  # refused before any warm-up


def test_a_pool_of_one_chip_a_node_sums_its_devices():
    kinds = json.loads((rig.BENCH / "devices.json").read_text())
    reports = {f"node{i}": {"platform": "tpu", "kind": "TPU v5 lite",
                            "count": 1, "pid": 10 + i} for i in range(4)}
    device = drive.pool_devices(reports, 4, platform="tpu",
                                device_kinds=kinds)
    assert device == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
                      "pids": [10, 11, 12, 13]}
    with pytest.raises(drive.NoAccelerator, match="3 device"):
        drive.pool_devices(dict(list(reports.items())[:3]), 4,
                           platform="tpu", device_kinds=kinds)
    # One node may own several chips: the check stays count >= chips.
    one = {"node0": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
                     "pid": 10}}
    assert drive.pool_devices(one, 4, platform="tpu",
                              device_kinds=kinds)["count"] == 4
