"""The paper's cluster cell, ``mandelbrot_paper.4node``, rehearsed on the
CPU: a ``nodes: 4`` copy of the configuration at a small plane runs end to
end through a real pool of four node-loaders (kernel in the Pallas
interpreter), every node does bands, and every job equals the plain
reference; and its two readers, ``chip_idle_share.max`` and
``node_band_share.min``, on hand-made runs."""

import json

import pytest

from bench import drive, readout, reference, run_cell
from bench.cell import load_cell
from bench.tests import rig
from bench.trace_reduce import ChipTrace, TraceSummary

CELL = "mandelbrot_paper.4node"


def test_a_cell_of_four_nodes_runs_end_to_end_on_cpu(tmp_path, monkeypatch,
                                                     capsys):
    # The cell's own configuration, at the tiny cell's plane and kernel.
    config = json.loads((rig.BENCH / "configs" /
                         "mandelbrot_paper_4node.json").read_text())
    tiny = json.loads((rig.BENCH / "tests" / "data" /
                       f"{rig.TINY}.json").read_text())
    small = {**config, **{k: tiny[k] for k in (
        "lines", "width", "max_iters", "workers", "interpret")}}
    assert small["nodes"] == 4
    root = rig.make_root(tmp_path, [rig.cell("tiny.4node", "batch", chips=4)],
                         config=small)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    recordings = []
    record = drive.record

    def keep(*args, **kwargs):
        recordings.append(record(*args, **kwargs))
        return recordings[-1]

    monkeypatch.setattr(drive, "record", keep)
    rc = run_cell.main(["--workload", "tiny.4node", "--seed",
                        str(2**31 + 17), "--seconds", "2", "--trace", "0"],
                       root=root, platform=None)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = rig.last_json_line(out.out)
    assert result["correct"] is True and result["failed"] == 0
    assert (result["device"]["platform"], result["device"]["count"]) == (
        "cpu", 4)
    [rec] = recordings
    assert len(set(rec.device["pids"])) == 4
    bands: dict[str, int] = {}
    for job in rec.jobs:
        for nid, n in (job.items_by_node or {}).items():
            bands[nid] = bands.get(nid, 0) + n
    assert sorted(bands) == [f"node{i}" for i in range(4)]
    assert min(bands.values()) > 0
    expected = reference.farm_answer(
        lines=small["lines"], width=small["width"],
        max_iters=small["max_iters"], escape_radius=small["escape_radius"],
        plane=small["plane"], lines_per_band=small["lines_per_band"])
    finished = [job.result for job in rec.jobs if job.result is not None]
    assert finished and all(r == expected for r in finished)


def _readers():
    cell = load_cell(rig.REPO, CELL)
    return cell, {m.name: m.reader for m in cell.per_layer}


def _job(by_node, done_at=1.0):
    job = drive.JobRecord(lines=16, bands=sum(by_node.values()), due=0.0)
    job.done_at, job.items_by_node = done_at, by_node
    return job


def _view(cell, jobs=(), chips=None):
    rec = drive.Recording(device={}, setup_s=1.0, window_start=0.0,
                          window_end=2.0, jobs=list(jobs),
                          points_per_band=40, memory_peak_bytes=None)
    trace = None
    if chips is not None:
        trace = TraceSummary(window=(0.0, 1e9), host=[], chips=[
            ChipTrace(ops=[], busy=[(0.0, busy * 1e9)], window=(0.0, 1e9),
                      host=[]) for busy in chips])
    return readout.RunView(cell=cell, rec=rec, trace=trace)


def test_the_cell_reports_its_six_per_layer_metrics():
    cell, readers = _readers()
    assert cell.chips == 4 and cell.config["nodes"] == 4
    assert sorted(readers) == [
        "chip_idle_share.max", "device_idle_share", "kernel_us_per_band",
        "launch_gap_us.p50", "node_band_share.min", "wire_bytes_per_band"]
    one = load_cell(rig.REPO, "mandelbrot_paper.batch")
    assert not {"chip_idle_share.max", "node_band_share.min"} & {
        m.name for m in one.per_layer}


@pytest.mark.parametrize("by_node,share", [
    ({"node0": 200, "node1": 200, "node2": 200, "node3": 200}, 1.0),
    ({"node0": 260, "node1": 260, "node2": 260, "node3": 20}, 0.1),
    ({"node0": 400, "node1": 200, "node2": 200}, 0.0),  # node3 did none
], ids=["even", "one_starved", "one_idle"])
def test_node_band_share_reads_the_smallest_nodes_share(by_node, share):
    cell, readers = _readers()
    half = {nid: n // 2 for nid, n in by_node.items()}
    view = _view(cell, [_job(half), _job(half),
                        _job({"node0": 800}, done_at=2.5)])  # past the close
    assert readers["node_band_share.min"].read(view) == pytest.approx(share)


def test_node_band_share_without_a_finished_job_reads_nothing():
    cell, readers = _readers()
    assert readers["node_band_share.min"].read(_view(cell)) is None


@pytest.mark.parametrize("busy,idlest", [
    ([0.25, 0.25, 0.25, 0.25], 75.0),
    ([0.25, 0.25, 0.05, 0.25], 95.0),  # one chip starved
], ids=["even", "one_idle_chip"])
def test_chip_idle_share_max_reads_the_idlest_chip(busy, idlest):
    cell, readers = _readers()
    view = _view(cell, chips=busy)
    assert readers["chip_idle_share.max"].read(view) == pytest.approx(idlest)
    assert readers["device_idle_share"].read(view) <= idlest
    assert readers["chip_idle_share.max"].read(_view(cell)) is None
