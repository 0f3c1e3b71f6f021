"""The Mandelbrot farm that ``chip_smoke.py`` drives, rehearsed on the CPU:
the same function, at a tiny size, through ``ClusterService`` with the
kernel in the Pallas interpreter, checked against the jnp oracle."""

import importlib.util
import json
import pathlib
import time

import numpy as np
import pytest

from repro.apps.mandelbrot_farm import (
    LINES_PER_ITEM,
    device_x_row,
    farm_grid,
    farm_spec,
    farm_totals,
)
from repro.kernels.mandelbrot.ops import band_totals, mandelbrot
from repro.kernels.mandelbrot.ref import (
    grid_coords,
    line_coords,
    line_ys,
    mandelbrot_reference,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_chip_smoke_farm_matches_oracle_on_cpu(chip_smoke):
    lines, width, max_iters = 24, 256, 64
    logged = []
    report = chip_smoke.run_farm(
        lines=lines, width=width, max_iters=max_iters, interpret=True,
        platform="cpu", workers=2,
        log=lambda *a, **_kw: logged.append(" ".join(map(str, a))))
    assert report["device"]["platform"] == "cpu"
    assert report["device"]["first_call_ms"] > 0
    assert report["node_backend_ms"] is not None  # started while booting
    assert report["band_points_differing"] == 0
    assert report["device"]["band_iters"].shape == (LINES_PER_ITEM, width)
    assert report["points_differing"] == 0
    assert report["oracle"]["points"] == lines * width
    cold, warm = report["submits"]
    assert cold["items_per_node"] == {"node0": lines // LINES_PER_ITEM}
    assert warm["items_per_node"] == {"node0": lines // LINES_PER_ITEM}
    assert (cold["code_shipped"], warm["code_shipped"]) == (1, 0)
    assert any(line.startswith("submit 2 (warm)") for line in logged)


def test_chip_smoke_without_tpu_exits_nonzero_and_prints_no_result(
        chip_smoke, capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # its node sees no TPU
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_farm_totals_sum_iterations_past_int32():
    """The paper grid's iteration sum can reach 1.79e10: totals are exact
    Python ints, not an int32 that wraps."""
    rows, width, max_iters = 3200, 5600, 1000
    iters = np.full((rows, width), max_iters, np.int32)
    colour = np.zeros((rows, width), np.int32)
    assert farm_totals(iters, colour) == (rows * width * max_iters, 0,
                                          rows * width)
    assert rows * width * max_iters > np.iinfo(np.int32).max
    x0, y0 = farm_grid(66, 100)  # whole bands only: 66 lines -> 16 bands
    assert x0.shape == y0.shape == (64, 100)


def test_band_coords_tile_the_whole_grid():
    """A band's coordinates are bit-identical to the same lines of the
    whole grid, which is what lets the farm match the oracle exactly."""
    x, y = grid_coords(12, 5600)
    for first in (0, 4, 8):
        bx, by = grid_coords(LINES_PER_ITEM, 5600, first_line=first)
        np.testing.assert_array_equal(bx, x[first:first + LINES_PER_ITEM])
        np.testing.assert_array_equal(by, y[first:first + LINES_PER_ITEM])
    lx, ly = line_coords(5600, 7)
    np.testing.assert_array_equal(lx, x[7])
    np.testing.assert_array_equal(ly, y[7])
    assert x.dtype == y.dtype == np.float32


@pytest.mark.parametrize("width,max_iters,bands", [
    (256, 48, range(10)),  # every band of a 40-line plane
    (5600, 6, (0, 399, 799)),  # the paper's width: first, middle, last
], ids=["small_plane", "paper_width"])
def test_band_program_matches_three_step_path_and_oracle(width, max_iters,
                                                         bands):
    """Band by band, the one-call band program (as the farm's work
    function runs it) returns what the three-step path (host grids, the
    kernel, two sums read back) and the jnp oracle return; the device-held
    x row and the band's y values are ``grid_coords``' own, bit for bit."""
    import jax.numpy as jnp

    spec = farm_spec(1, 1, lines=4 * LINES_PER_ITEM, width=width,
                     max_iters=max_iters, interpret=True)
    work = spec.node_net.group.function
    row = np.asarray(device_x_row(width))
    assert row.shape == (1, width) and row.dtype == np.float32
    for band in bands:
        first = band * LINES_PER_ITEM
        x0, y0 = grid_coords(LINES_PER_ITEM, width, first_line=first)
        ys = line_ys(LINES_PER_ITEM, width, first_line=first)
        np.testing.assert_array_equal(np.broadcast_to(row, x0.shape), x0)
        np.testing.assert_array_equal(
            np.broadcast_to(ys[:, None], y0.shape), y0)
        assert ys.dtype == np.float32

        iters, colour = mandelbrot(x0, y0, max_iters=max_iters,
                                   interpret=True)
        three_step = (int(jnp.sum(iters)), int(jnp.sum(colour)), colour.size)
        ref_iters, ref_colour = mandelbrot_reference(
            jnp.asarray(x0), jnp.asarray(y0), max_iters)
        oracle = farm_totals(np.asarray(ref_iters), np.asarray(ref_colour))
        totals = band_totals(device_x_row(width), ys[:, None],
                             max_iters=max_iters, interpret=True)
        assert totals.dtype == jnp.int32 and totals.shape == (2,)
        answer = work(band)
        assert answer == three_step == oracle, band
        assert tuple(np.asarray(totals).tolist()) == answer[:2]
        assert all(type(v) is int for v in answer)


def test_device_x_row_is_put_once_under_racing_workers():
    """More threads than cores ask for a new width's x row at once, with a
    short switch interval: every one gets the same device array, and the
    process counts one upload."""
    import os
    import sys
    import threading

    from repro.cluster.telemetry import counters

    width = 4224  # no other test puts this width on the device
    n = (os.cpu_count() or 1) + 1
    before = counters.snapshot().get("band_row_uploads", 0)
    rows, start = [], threading.Barrier(n)

    def worker():
        start.wait(timeout=30)
        rows.append(device_x_row(width))

    threads = [threading.Thread(target=worker) for _ in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(rows) == n and all(row is rows[0] for row in rows)
    assert counters.snapshot()["band_row_uploads"] - before == 1


def test_band_program_compiles_once_across_line_counts():
    """A 4-band farm and then a 12-band farm through one node process:
    the band program's jit cache holds one entry, the node put the x row
    on its device once (``band_row_uploads`` on its heartbeat reads 1),
    and the second farm traced and compiled nothing."""
    from repro.cluster.service import ClusterService

    def jit_entries(_item):
        from repro.kernels.mandelbrot.ops import band_totals

        return band_totals._cache_size()

    width, max_iters = 256, 32
    x0, y0 = farm_grid(12 * LINES_PER_ITEM, width)
    iters, colour = mandelbrot_reference(x0, y0, max_iters)
    iters, colour = np.asarray(iters), np.asarray(colour)
    svc = ClusterService(nodes=1, workers=2,
                         preload=("repro.kernels.mandelbrot.ops",))
    counts = []
    with svc:
        for bands in (4, 12):
            lines = bands * LINES_PER_ITEM
            spec = farm_spec(1, 2, lines=lines, width=width,
                             max_iters=max_iters, interpret=True)
            assert svc.submit(spec, timeout=120).result() == farm_totals(
                iters[:lines], colour[:lines])
            time.sleep(1.2)  # a heartbeat carries the node's counts
            counts.append(dict(
                svc.metrics_snapshot()["nodes"]["node0"]["compiles"]))
        entries = svc.submit(_one_item(jit_entries), timeout=60).result()
    assert entries == 1
    assert counts[0]["band_row_uploads"] == counts[1]["band_row_uploads"] == 1
    for key in ("jaxpr_traces", "backend_compiles"):
        assert counts[0][key] >= 1
        assert counts[1][key] == counts[0][key], key


def _one_item(work):
    """A job of one item whose work function is ``work``: how spans are
    turned on and collected in a node-loader."""
    from repro.core.dsl import ClusterSpec
    from repro.core.processes import EmitDetails, ResultDetails

    return ClusterSpec.simple(
        host="127.0.0.1", nclusters=1, workers_per_node=1,
        emit_details=EmitDetails(
            name="one", init=lambda: False,
            create=lambda sent: (None, sent) if sent else (0, True)),
        work_function=work,
        result_details=ResultDetails(name="one", init=lambda: None,
                                     collect=lambda _acc, r: r))


def test_farm_spans_cover_every_band_and_add_no_wire_bytes(monkeypatch):
    """A real one-node farm on the CPU, its spans off and then on in host
    and node: with spans on, every band has one ``node.queue``,
    ``node.work`` and ``node.hold`` span with its (job, item), the band
    program's spans inherit those ids, and the bands' data frames carry
    the same bytes as with spans off (no field rides the wire for them).
    One worker, no prefetch and a flush per result make each band one
    WORK_BATCH and one RESULT_BATCH of one item, so bytes per band repeat
    exactly."""
    from repro.cluster.service import ClusterService
    from repro.cluster.telemetry import spans
    from repro.cluster.wire import FrameConnection, FrameType

    data_bytes: dict[int, int] = {}
    send, recv = FrameConnection.send, FrameConnection.recv

    def counted_send(self, frame):
        before = self.counters.bytes_sent
        send(self, frame)
        if frame.ftype is FrameType.WORK_BATCH:
            data_bytes[frame.job_id] = (data_bytes.get(frame.job_id, 0)
                                        + self.counters.bytes_sent - before)

    def counted_recv(self):
        before = self.counters.bytes_recv
        frame = recv(self)
        if frame.ftype is FrameType.RESULT_BATCH:
            data_bytes[frame.job_id] = (data_bytes.get(frame.job_id, 0)
                                        + self.counters.bytes_recv - before)
        return frame

    monkeypatch.setattr(FrameConnection, "send", counted_send)
    monkeypatch.setattr(FrameConnection, "recv", counted_recv)
    lines, bands = 32, 32 // LINES_PER_ITEM
    spec = farm_spec(1, 1, lines=lines, width=256, max_iters=32,
                     interpret=True)
    svc = ClusterService(nodes=1, workers=1, prefetch=0, flush_items=1,
                         preload=("repro.kernels.mandelbrot.ops",))
    try:
        with svc:
            off = svc.submit(spec, timeout=120)
            answer = off.result()
            time.sleep(1.2)  # a heartbeat carries the node's counts
            compiles = {k: v for k, v in svc.metrics_snapshot()["nodes"]
                        ["node0"]["compiles"].items()
                        if k in ("jaxpr_traces", "backend_compiles")}
            svc.submit(_one_item(spans.enable), timeout=60).result()
            spans.enable()
            on = svc.submit(spec, timeout=120)
            assert on.result() == answer
            svc.submit(_one_item(spans.disable), timeout=60).result()
            node_spans, node_dropped = svc.submit(
                _one_item(spans.drain), timeout=60).result()
            # Off only now: the job's last host.collect closes after its
            # result is released to the caller.
            spans.disable()
            time.sleep(1.2)
            counts = svc.metrics_snapshot()["nodes"]["node0"]["compiles"]
    finally:
        spans.disable()
    host_spans, host_dropped = spans.drain()
    assert (node_dropped, host_dropped) == (0, 0)
    # The kernel compiled in the first job, and not again in the second.
    assert compiles["jaxpr_traces"] >= 1 and compiles["backend_compiles"] >= 1
    assert {k: counts[k] for k in compiles} == compiles
    assert counts["backend_compiles_ms"] > 0

    assert data_bytes[off.job_id] == data_bytes[on.job_id]
    assert data_bytes[on.job_id] > 0

    job = on.job_id
    per_band = {}
    for name, start, end, span_id, parent, thread, ids in node_spans:
        assert end >= start
        if ids.get("job") == job and "item" in ids:
            per_band.setdefault(name, []).append(ids["item"])
    for name in ("node.queue", "node.work", "node.hold", "band.coords",
                 "band.call", "band.readback"):
        assert sorted(per_band[name]) == list(range(bands)), name
    work = {r[3]: r for r in node_spans if r[0] == "node.work"}
    for r in node_spans:
        if r[0].startswith("band."):
            parent = work[r[4]]  # each is a child of its band's node.work
            assert parent[6] == r[6]
            assert parent[1] <= r[1] <= r[2] <= parent[2]
    assert any(r[0] == "node.idle" and r[6] == {"worker": 0}
               for r in node_spans)
    assert any(r[0] == "node.flush" and r[6]["job"] == job
               for r in node_spans)
    names = [r[0] for r in host_spans if r[6].get("job") == job]
    assert names.count("service.admit") == 1
    assert names.count("host.dispatch") == bands
    assert names.count("host.collect") == bands
