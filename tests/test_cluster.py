"""The multi-process cluster transport (repro.cluster), on localhost sockets.

Covers the acceptance path of the real Host-Node-Loader deployment: wire
format, socket channels, membership thresholds, bootstrap across real
subprocesses, demand-driven distribution (straggler bias), node death
mid-job with no lost or duplicated work, and clean UT shutdown with no
orphaned processes.  Everything runs on 127.0.0.1 with ephemeral ports, so
tier-1 stays hermetic.

Work functions are defined *inside* the tests: cloudpickle then ships them
by value over the LOAD frame (the code-loading channel), which also means
the node-loader subprocesses never import this test module (or jax).
"""

import socket
import threading
import time

import pytest

from repro.cluster.membership import DEAD, DONE, Membership
from repro.cluster.netchannels import ChannelClosed, ChannelMux
from repro.cluster.wire import (
    APP_WIRE_CHANNEL,
    LOAD_WIRE_CHANNEL,
    UT,
    Frame,
    FrameConnection,
    FrameType,
    pack_frame,
    unpack_frame,
)
from repro.core.builder import ClusterBuilder
from repro.core.dsl import ClusterSpec
from repro.core.processes import EmitDetails, ResultDetails
from repro.runtime.failures import HeartbeatMonitor

# Fast liveness settings for tests (death detected within ~0.4s).
FAST = dict(heartbeat_interval=0.1, heartbeat_misses=4)


def _range_emit(n):
    return EmitDetails(
        name="range",
        init=lambda limit: (0, limit),
        init_data=(n,),
        create=lambda s: (None, s) if s[0] >= s[1] else (s[0], (s[0] + 1, s[1])),
    )


def _sum_collect():
    return ResultDetails(name="sum", init=lambda: 0,
                         collect=lambda a, x: a + x)


def _spec(nclusters, workers, n_items, work):
    return ClusterSpec.simple(
        host="127.0.0.1", nclusters=nclusters, workers_per_node=workers,
        emit_details=_range_emit(n_items), work_function=work,
        result_details=_sum_collect(),
    )


# ---------------------------------------------------------------------------
# wire
# ---------------------------------------------------------------------------


def test_wire_frame_roundtrip_msgpack_and_pickle():
    # plain data -> msgpack codec; frames round-trip exactly
    f = Frame(FrameType.HEARTBEAT, {"node_id": "node0"}, LOAD_WIRE_CHANNEL)
    g = unpack_frame(pack_frame(f))
    assert g.ftype is FrameType.HEARTBEAT
    assert g.payload == {"node_id": "node0"}
    assert g.channel == LOAD_WIRE_CHANNEL

    # tuples are NOT msgpack-safe (would come back as lists) -> pickle codec
    f = Frame(FrameType.WORK, {"id": 3, "obj": (1, 2)}, APP_WIRE_CHANNEL)
    g = unpack_frame(pack_frame(f))
    assert g.payload["obj"] == (1, 2)
    assert isinstance(g.payload["obj"], tuple)

    # functions (shipped code) survive
    f = Frame(FrameType.LOAD, {"function": lambda x: x + 41})
    g = unpack_frame(pack_frame(f))
    assert g.payload["function"](1) == 42

    # ints beyond the msgpack 64-bit range take the pickle path
    g = unpack_frame(pack_frame(Frame(FrameType.RESULT, {"value": 2**70})))
    assert g.payload["value"] == 2**70

    # empty payload + UT
    g = unpack_frame(pack_frame(Frame(FrameType.UT, None)))
    assert g.ftype is FrameType.UT and g.payload is None


def test_wire_rejects_corrupt_header():
    raw = bytearray(pack_frame(Frame(FrameType.WORK_REQUEST, {"node_id": "n"})))
    raw[0:4] = b"XXXX"
    with pytest.raises(ValueError, match="magic"):
        unpack_frame(bytes(raw))


def test_netchannel_mux_blocking_roundtrip_and_close():
    a, b = socket.socketpair()
    left, right = FrameConnection(a), FrameConnection(b)
    mux_l, mux_r = ChannelMux(left), ChannelMux(right)
    ch_l = mux_l.open(APP_WIRE_CHANNEL, FrameType.WORK)
    ch_r = mux_r.open(APP_WIRE_CHANNEL, FrameType.WORK)
    mux_l.start()
    mux_r.start()

    ch_l.put({"id": 0, "obj": 7})
    assert ch_r.get(timeout=5) == {"id": 0, "obj": 7}
    ch_r.put(UT)
    assert ch_l.get(timeout=5) is UT

    mux_r.close()
    with pytest.raises(ChannelClosed):
        ch_l.get(timeout=5)
    mux_l.close()


# ---------------------------------------------------------------------------
# membership
# ---------------------------------------------------------------------------


def test_membership_heartbeat_threshold_declares_death():
    m = Membership(HeartbeatMonitor(interval_s=0.1, misses=3))
    m.register("node0", "127.0.0.1:1", now=0.0)
    m.register("node1", "127.0.0.1:2", now=0.0)
    m.beat("node1", now=0.5)
    # node0 silent for > 0.3s -> dead; node1 beat recently -> alive
    dead = m.reap(now=0.6, at_item=12)
    assert [r.node_id for r in dead] == ["node0"]
    assert m.nodes["node0"].state == DEAD
    assert m.nodes["node1"].alive
    ev = m.failures[0]
    assert ev.kind == "node_loss" and ev.node == 0 and ev.step == 12
    # reap is idempotent; a late beat from the dead node is ignored
    m.beat("node0", now=0.7)
    assert m.reap(now=0.8) == []
    m.mark_done("node1", {"items": 5})
    assert m.finished()


def test_membership_booting_node_misses_count_only_after_grace():
    """A node that registered as booting is not reaped for silence until
    it reports its boot done, or ``boot_grace_s`` after registering, or
    its connection closes."""
    m = Membership(HeartbeatMonitor(interval_s=0.1, misses=3),
                   boot_grace_s=2.0)
    m.register("node0", "127.0.0.1:1", booting=True, now=0.0)
    m.register("node1", "127.0.0.1:2", booting=True, now=0.0)
    m.register("node2", "127.0.0.1:3", booting=True, now=0.0)
    m.end_boot("node2")  # its socket closed mid-boot
    assert [r.node_id for r in m.reap(now=1.0)] == ["node2"]
    m.beat("node1", now=1.5)  # boot done: plain deadline from here on
    assert m.reap(now=1.7) == []
    assert [r.node_id for r in m.reap(now=1.9)] == ["node1"]
    assert m.nodes["node0"].alive  # still booting, within its grace
    assert [r.node_id for r in m.reap(now=2.1)] == ["node0"]


# ---------------------------------------------------------------------------
# the real thing: subprocess clusters on localhost
# ---------------------------------------------------------------------------


def test_node_holding_interpreter_lock_at_boot_is_not_declared_dead():
    """A node whose boot holds the interpreter lock (as a TPU client's
    start does) sends no heartbeat meanwhile; the host must not count
    that silence as death, and the node then serves jobs as usual."""
    from repro.cluster.service import ClusterService

    def work(x):
        return x + 1

    svc = ClusterService(nodes=1, workers=1, preload=("_boot_stall",),
                         **FAST)
    try:
        with svc:
            handle = svc.submit(_spec(1, 1, 8, work), timeout=60.0)
            assert handle.result() == sum(range(1, 9))
            assert svc.host_loader.stats.deaths_detected == 0
            report = svc.metrics_snapshot()["nodes"]["node0"]["report"]
            assert report["booting"] is False
            assert report["boot_ms"] > 1000  # the stall was inside boot
    finally:
        assert svc.orphaned() == []


def test_cluster_backend_bootstraps_and_completes():
    """ClusterSpec -> backend="cluster" -> >= 2 real subprocesses -> exact
    result, per-node timing returned, clean UT shutdown, no orphans."""

    def work(x):
        return x * x

    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(2, 2, 40, work), backend="cluster", job_timeout=120.0, **FAST
    )
    assert app.run() == sum(i * i for i in range(40))

    # demand-driven totals: every item processed exactly once
    stats = app.host_loader.stats
    assert stats.items_total == 40
    assert stats.redispatched == 0 and stats.deaths_detected == 0

    # both node-loaders were real OS processes and exited cleanly on UT
    assert len(app.processes) == 2
    assert app.orphaned() == []
    assert all(p.returncode == 0 for p in app.processes.values())
    assert all(r.state == DONE
               for r in app.host_loader.membership.nodes.values())

    # requirement 7: nodes returned their (load, run) timing to the host
    by_id = {t.node_id: t for t in builder.timing.nodes}
    assert {"host", "node0", "node1"} <= set(by_id)
    assert by_id["node0"].items + by_id["node1"].items == 40
    assert by_id["node0"].run_ms > 0 and by_id["node1"].run_ms > 0


def test_demand_driven_distribution_biases_against_straggler():
    """An artificially slowed node must receive measurably fewer items — the
    onrl/nrfa protocol only answers *requests*, it never pushes."""

    def work(x):
        time.sleep(0.005)
        return x + 1

    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(2, 1, 40, work), backend="cluster", job_timeout=120.0,
        slowdown={"node1": 0.05}, **FAST
    )
    assert app.run() == sum(i + 1 for i in range(40))
    items = {t.node_id: t.items for t in builder.timing.nodes
             if t.node_id.startswith("node")}
    assert items["node0"] + items["node1"] == 40
    assert items["node1"] < items["node0"], items
    # ~10x slower per item -> well under half the work
    assert items["node1"] <= 40 // 2 - 2, items
    assert app.orphaned() == []


def test_node_death_is_detected_and_work_redispatched():
    """SIGKILL one node-loader mid-job: missed heartbeats declare it dead,
    its in-flight items are re-dispatched, and the survivors finish with no
    item lost or duplicated (the sum is exact)."""

    def work(x):
        time.sleep(0.03)
        return 3 * x

    n_items = 60
    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(3, 1, n_items, work), backend="cluster", job_timeout=120.0,
        **FAST
    )
    runner = app.run_async()
    while app.host_loader is None or app.host_loader.stats.items_total < 5:
        time.sleep(0.02)
        assert runner.is_alive()
    app.kill_node("node1")
    runner.join(timeout=120)
    assert not runner.is_alive(), "cluster hung after node death"

    assert app.result == sum(3 * i for i in range(n_items))
    hl = app.host_loader
    assert hl.stats.deaths_detected == 1
    assert hl.stats.items_total == n_items
    assert hl.stats.duplicates_dropped == 0
    # detection fed the real failure path: a node_loss FailureEvent
    [ev] = hl.membership.failures
    assert ev.kind == "node_loss"
    assert hl.membership.nodes["node1"].state == DEAD
    # survivors shut down cleanly; the killed process is reaped too
    assert app.orphaned() == []
    assert app.processes["node0"].returncode == 0
    assert app.processes["node2"].returncode == 0
    assert app.processes["node1"].returncode != 0


def test_all_nodes_dead_raises_instead_of_hanging():
    def work(x):
        time.sleep(0.05)
        return x

    app = ClusterBuilder().build_application(
        _spec(1, 1, 50, work), backend="cluster", job_timeout=60.0, **FAST
    )
    runner = app.run_async()
    while app.host_loader is None or app.host_loader.stats.items_total < 2:
        time.sleep(0.02)
        assert runner.is_alive()
    app.kill_node("node0")
    runner.join(timeout=60)
    assert not runner.is_alive()
    assert app.result is None
    assert isinstance(app.error, RuntimeError)
    assert "died with work outstanding" in str(app.error)
    assert app.orphaned() == []


def test_work_function_exception_fails_job_with_node_traceback():
    """A raising work function must fail the job promptly (reported by the
    node, raised at the host) — not stall until job_timeout with a silently
    dead worker thread."""

    def work(x):
        if x == 7:
            raise ValueError("item 7 is cursed")
        return x

    app = ClusterBuilder().build_application(
        _spec(2, 1, 20, work), backend="cluster", job_timeout=60.0, **FAST
    )
    runner = app.run_async()
    runner.join(timeout=60)
    assert not runner.is_alive()
    assert app.result is None
    from repro.cluster.host_loader import WorkFunctionError

    assert isinstance(app.error, WorkFunctionError)
    assert "item 7 is cursed" in str(app.error)
    assert app.orphaned() == []


def test_pipelined_dispatch_batches_frames_and_counts_wire_traffic():
    """The credit pipeline must move N items in far fewer than the 3N frames
    of the one-item-per-round-trip protocol (request + work + result each),
    and the host must fold wire counters into the timing collector."""

    def work(x):
        return x + 1

    n_items = 200
    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(2, 2, n_items, work), backend="cluster", job_timeout=120.0,
        flush_items=16, **FAST
    )
    assert app.run() == sum(i + 1 for i in range(n_items))

    stats = app.host_loader.stats
    assert stats.items_total == n_items
    # Items travelled in batches, not one frame each...
    assert stats.work_batches < n_items
    assert stats.result_batches < n_items
    assert stats.max_batch > 1
    # ...and each node issued one explicit windowed request; all other
    # demand piggybacked on result deliveries.
    assert stats.work_requests == 2

    wire_counts = builder.timing.wire
    assert wire_counts["bytes_sent"] > 0 and wire_counts["bytes_recv"] > 0
    assert wire_counts["round_trips"] == (
        stats.work_requests + stats.result_batches
    )
    # The app channel moved well under 2 host-bound frames per item
    # (heartbeats ride the same sockets, so allow them some headroom).
    assert wire_counts["frames_recv"] < 2 * n_items

    # requirement 7 extension: boot is accounted separately from load.
    by_id = {t.node_id: t for t in builder.timing.nodes}
    assert by_id["node0"].boot_ms >= 0.0
    assert by_id["node0"].load_ms > 0.0
    assert app.orphaned() == []


def test_prefetch_zero_gives_strict_per_worker_window():
    """prefetch=0 must be honoured (not clamped): the node buffers exactly
    one item per worker — the pure demand-driven pre-pipelining window."""

    def work(x):
        return x * 2

    app = ClusterBuilder().build_application(
        _spec(1, 2, 30, work), backend="cluster", job_timeout=60.0,
        prefetch=0, **FAST
    )
    assert app.run() == sum(2 * i for i in range(30))
    # window == workers -> the single up-front request asked for exactly 2.
    assert app.host_loader.stats.max_batch <= 2
    assert app.orphaned() == []


def test_unencodable_work_item_fails_job_instead_of_requeue_loop():
    """An item no wire codec can carry must fail the job loudly — not be
    mistaken for a dead pipe and requeued forever (regression)."""
    deep = []
    for _ in range(100_000):
        deep = [deep]

    spec = ClusterSpec.simple(
        host="127.0.0.1", nclusters=1, workers_per_node=1,
        emit_details=EmitDetails(
            name="deep", init=lambda: 0, init_data=(),
            create=lambda s: (None, s) if s else (deep, 1),
        ),
        work_function=lambda x: 0,
        result_details=_sum_collect(),
    )
    app = ClusterBuilder().build_application(
        spec, backend="cluster", job_timeout=60.0, **FAST
    )
    runner = app.run_async()
    runner.join(timeout=60)
    assert not runner.is_alive()
    assert isinstance(app.error, ValueError)
    assert "nested too deeply" in str(app.error)
    assert app.orphaned() == []


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="backend"):
        ClusterBuilder().build_application(
            _spec(1, 1, 1, lambda x: x), backend="mpi"
        )
    with pytest.raises(TypeError, match="options"):
        ClusterBuilder().build_application(
            _spec(1, 1, 1, lambda x: x), backend="threads", port=1234
        )


def test_same_spec_same_result_on_both_backends():
    """Zero user-code changes between threads and processes (§6.1)."""

    def work(x):
        return (x, x * 2)  # tuple payload: exercises the pickle codec path

    def collect(acc, item):
        return acc + item[0] + item[1]

    def make():
        return ClusterSpec.simple(
            host="127.0.0.1", nclusters=2, workers_per_node=2,
            emit_details=_range_emit(30), work_function=work,
            result_details=ResultDetails(name="s", init=lambda: 0,
                                         collect=collect),
        )

    threaded = ClusterBuilder().build_application(make()).run()
    processed = ClusterBuilder().build_application(
        make(), backend="cluster", job_timeout=120.0, **FAST
    ).run()
    assert threaded == processed == sum(3 * i for i in range(30))
