"""The persistent warm cluster service (repro.cluster.service).

One pool, many jobs: concurrent submissions interleaving on the same
nodes with exactly-once preserved per job (including through a mid-run
node death), warm resubmission skipping both boot and code shipping,
FIFO-with-priority admission, failure isolation between jobs, and the
``backend="service"`` builder path.  Everything runs on 127.0.0.1 with an
InProcessLauncher (real sockets, real frames, no subprocess cost), so
tier-1 stays hermetic.
"""

import time

import pytest

from repro.cluster.deploy.inprocess import InProcessLauncher
from repro.cluster.service import ClusterService
from repro.core.builder import ClusterBuilder
from repro.core.dsl import ClusterSpec
from repro.core.processes import EmitDetails, ResultDetails

# Fast liveness settings (death detected within ~0.4s).
FAST = dict(heartbeat_interval=0.1, heartbeat_misses=4)


def _range_emit(n):
    return EmitDetails(
        name="range",
        init=lambda limit: (0, limit),
        init_data=(n,),
        create=lambda s: (None, s) if s[0] >= s[1] else (s[0], (s[0] + 1, s[1])),
    )


def _list_collect():
    return ResultDetails(name="list", init=lambda: [],
                         collect=lambda a, x: a + [x], finalise=sorted)


def _spec(work, n_items, *, nclusters=2, workers=2):
    return ClusterSpec.simple(
        host="127.0.0.1", nclusters=nclusters, workers_per_node=workers,
        emit_details=_range_emit(n_items), work_function=work,
        result_details=_list_collect(),
    )


def _service(**kw):
    kw.setdefault("nodes", 2)
    kw.setdefault("workers", 2)
    kw.setdefault("launcher", InProcessLauncher())
    kw.update(FAST)
    return ClusterService(**kw)


# Module-level work functions: the same object on every submit, so their
# cloudpickle digests match and resubmits hit the nodes' code caches.
def _double(x):
    return x * 2


def _triple(x):
    return x * 3


# ---------------------------------------------------------------------------
# one pool, many jobs
# ---------------------------------------------------------------------------


def test_back_to_back_jobs_one_pool():
    """Sequential submits reuse the booted pool: only the first submission
    is charged boot time, and both produce exact results."""
    with _service() as svc:
        h1 = svc.submit(_spec(_double, 30), timeout=60)
        assert h1.result() == [2 * i for i in range(30)]
        h2 = svc.submit(_spec(_triple, 30), timeout=60)
        assert h2.result() == [3 * i for i in range(30)]
        assert h1.cluster_boot_ms > 0.0
        assert h2.cluster_boot_ms == 0.0
    assert svc.orphaned() == []


def test_concurrent_jobs_interleave_exactly_once():
    """Two jobs submitted together share the node pool; each collects its
    own items exactly once (no cross-job leakage, no loss, no dupes)."""
    with _service() as svc:
        h1 = svc.submit(_spec(_double, 40), timeout=60)
        h2 = svc.submit(_spec(_triple, 40), timeout=60)
        r1, r2 = h1.result(), h2.result()
        assert r1 == [2 * i for i in range(40)]
        assert r2 == [3 * i for i in range(40)]
        assert h1.stats()["items_collected"] == 40
        assert h2.stats()["items_collected"] == 40
    assert svc.orphaned() == []


def test_node_death_mid_run_both_jobs_complete():
    """A node dying with in-flight items of *both* jobs: the host reaps it,
    requeues per job, and the surviving node finishes both exactly-once."""

    def slow_double(x):
        time.sleep(0.005)
        return x * 2

    def slow_triple(x):
        time.sleep(0.005)
        return x * 3

    n = 60
    with _service() as svc:
        h1 = svc.submit(_spec(slow_double, n), timeout=120)
        h2 = svc.submit(_spec(slow_triple, n), timeout=120)
        hl = svc.host_loader
        deadline = time.monotonic() + 30
        while hl.stats.items_total < 10:  # both jobs under way
            assert time.monotonic() < deadline
            time.sleep(0.005)
        svc.kill_node("node1")
        assert h1.result() == [2 * i for i in range(n)]
        assert h2.result() == [3 * i for i in range(n)]
        assert hl.stats.deaths_detected == 1
        assert hl.stats.redispatched > 0
    assert svc.orphaned() == []


def test_priority_preempts_fifo():
    """A high-priority job submitted behind a long low-priority one is
    answered first at every demand: it finishes while the long job is
    still running."""

    def slow(x):
        time.sleep(0.01)
        return x

    with _service(nodes=1, workers=1) as svc:
        h_low = svc.submit(_spec(slow, 100, nclusters=1, workers=1),
                           priority=0, timeout=120)
        h_high = svc.submit(_spec(_double, 5, nclusters=1, workers=1),
                            priority=5, timeout=120)
        assert h_high.result() == [2 * i for i in range(5)]
        assert not h_low.done()  # the long job is still going
        assert h_low.result() == list(range(100))
    assert svc.orphaned() == []


# ---------------------------------------------------------------------------
# warm resubmission
# ---------------------------------------------------------------------------


def test_warm_resubmit_skips_boot_and_code():
    """Resubmitting a pipeline whose stage function the nodes already hold:
    no boot, no code shipped — the nodes rebind from their digest cache."""
    with _service() as svc:
        h1 = svc.submit(_spec(_double, 20), timeout=60)
        h1.result()
        s1 = h1.stats()
        assert s1["code_shipped"] > 0 and s1["code_cached"] == 0

        h2 = svc.submit(_spec(_double, 20), timeout=60)
        assert h2.result() == h1.result()
        s2 = h2.stats()
        assert s2["cluster_boot_ms"] == 0.0
        assert s2["code_shipped"] == 0  # every node served it from cache
        assert s2["code_cached"] == s1["code_shipped"]
        assert h2.submit_to_first_result_ms is not None


def test_failed_job_does_not_poison_the_pool():
    """A work-function error fails *that* job only; the pool stays warm and
    the next submission runs normally."""

    def cursed(x):
        if x == 7:
            raise ValueError("item 7 is cursed")
        return x

    with _service() as svc:
        h_bad = svc.submit(_spec(cursed, 20), timeout=60)
        with pytest.raises(Exception, match="item 7 is cursed"):
            h_bad.result()
        h_ok = svc.submit(_spec(_double, 20), timeout=60)
        assert h_ok.result() == [2 * i for i in range(20)]
    assert svc.orphaned() == []


def test_submit_after_close_rejected():
    svc = _service()
    svc.start()
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(_spec(_double, 5))


# ---------------------------------------------------------------------------
# builder integration (backend="service")
# ---------------------------------------------------------------------------


def test_builder_service_backend_ephemeral_pool():
    """backend="service" with no service= boots an ephemeral pool sized
    from the spec and tears it down after — the one-shot contract."""
    app = ClusterBuilder().build_application(
        _spec(_double, 25), backend="service",
        launcher=InProcessLauncher(), **FAST,
    )
    assert app.run() == [2 * i for i in range(25)]
    assert app.orphaned() == []


def test_builder_service_backend_shared_warm_pool():
    """Two applications over one caller-owned service: the second build of
    the same spec is a warm resubmit (no boot, no code shipped)."""
    with _service() as svc:
        b = ClusterBuilder()
        app1 = b.build_application(_spec(_triple, 15), backend="service",
                                   service=svc)
        app2 = b.build_application(_spec(_triple, 15), backend="service",
                                   service=svc)
        assert app1.run() == [3 * i for i in range(15)]
        assert app2.run() == app1.result
        assert app2.handle.cluster_boot_ms == 0.0
        assert app2.handle.stats()["code_shipped"] == 0
        # the shared pool survives its applications
        assert svc.run(_spec(_double, 5)) == [0, 2, 4, 6, 8]
    assert svc.orphaned() == []


# ---------------------------------------------------------------------------
# elasticity (grow / graceful shrink)
# ---------------------------------------------------------------------------


def _wait_pool(svc, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(svc.pool_alive()) == n:
            return
        time.sleep(0.05)
    raise AssertionError(f"pool never reached {n}: {svc.pool_alive()}")


def test_grow_adds_nodes_via_late_join():
    """grow() launches fresh node-loaders into the running pool: they
    register mid-run, receive the pool config, and serve work."""
    with _service(nodes=1) as svc:
        assert svc.run(_spec(_double, 10)) == [2 * i for i in range(10)]
        new_ids = svc.grow(1)
        assert new_ids == ["node1"]
        _wait_pool(svc, 2)
        h = svc.submit(_spec(_double, 40), timeout=60)
        assert h.result() == [2 * i for i in range(40)]
        # Both the original and the late-joined node did work eventually
        # (the pool is 2-wide; at minimum the grown node is a live member).
        assert svc.pool_alive() == ["node0", "node1"]
        assert svc.telemetry.snapshot()["cluster"]["scale_up_events"] == 1
    assert svc.orphaned() == []


def test_shrink_retires_node_gracefully():
    """shrink() fences the victim and UTs it: the pool contracts without a
    death event, and jobs keep producing exact results before and after."""
    with _service(nodes=2) as svc:
        assert svc.run(_spec(_double, 10)) == [2 * i for i in range(10)]
        retired = svc.shrink()
        assert retired == "node1"
        _wait_pool(svc, 1)
        assert svc.run(_spec(_double, 20)) == [2 * i for i in range(20)]
        snap = svc.telemetry.snapshot()["cluster"]
        assert snap["scale_down_events"] == 1
        assert svc.host_loader.membership.failures == []  # no death, a retire
        # The last live node is never retirable.
        assert svc.shrink() is None
    assert svc.orphaned() == []


def test_grow_then_shrink_round_trip():
    with _service(nodes=1) as svc:
        svc.start()
        svc.grow(1)
        _wait_pool(svc, 2)
        assert svc.shrink() == "node1"
        _wait_pool(svc, 1)
        assert svc.run(_spec(_triple, 12)) == [3 * i for i in range(12)]
    assert svc.orphaned() == []


def test_grow_launch_failure_retracts_announcement():
    """A launch that raises must not leave a phantom LAUNCHING record:
    the announcement is retracted, pool_span() stops counting it as
    capacity on its way (a phantom would suppress autoscale scale-ups
    forever), and no scale_up event is recorded."""
    with _service(nodes=1) as svc:
        assert svc.run(_spec(_double, 6)) == [2 * i for i in range(6)]

        def boom(node_id, **kw):
            raise RuntimeError("launcher out of capacity")

        svc.launcher.launch = boom
        with pytest.raises(RuntimeError, match="out of capacity"):
            svc.grow(1)
        # Wait for the dispatcher to process both the announcement and
        # its retraction (pool_span alone could read (1, 0) before the
        # expect event was even applied).
        deadline = time.monotonic() + 10
        while True:
            rec = svc.host_loader.membership.nodes.get("node1")
            if rec is not None and rec.state == "dead":
                break
            assert time.monotonic() < deadline, "retraction never applied"
            time.sleep(0.02)
        assert svc.pool_span() == (1, 0)
        snap = svc.telemetry.snapshot()["cluster"]
        assert snap.get("scale_up_events", 0) == 0
    assert svc.orphaned() == []


# ---------------------------------------------------------------------------
# per-stage data-plane knobs on the shared pool
# ---------------------------------------------------------------------------


def test_pool_job_honours_stage_prefetch_cap():
    """A service-pool job's per-stage prefetch= bounds how many of its
    items one node may hold: with prefetch=0 no WORK_BATCH can exceed the
    pool's worker count, where an uncapped job batches the full credit
    window."""
    from repro.core.dsl import PipelineSpec, Stage

    def capped_spec(n):
        return PipelineSpec.simple(
            host="127.0.0.1", emit_details=_range_emit(n),
            stages=[Stage(name="double", fn=_double, nclusters=1,
                          workers_per_node=2, prefetch=0, flush_ms=1.0)],
            result_details=_list_collect(),
        )

    with _service(nodes=1, workers=2) as svc:
        h = svc.submit(capped_spec(40), timeout=60)
        assert h.result() == [2 * i for i in range(40)]
        assert svc.host_loader.stats.max_batch <= 2  # pool_workers + 0
    assert svc.orphaned() == []


def test_the_host_logs_each_node_death_with_its_state(caplog):
    """A node declared dead is logged with its id, its silence, its items
    in flight and whether its process still runs; the bus's failure event
    carries the same."""

    def slow_double(x):
        time.sleep(0.01)
        return x * 2

    n = 60
    with caplog.at_level("WARNING", logger="repro.cluster.host_loader"):
        with _service() as svc:
            h = svc.submit(_spec(slow_double, n), timeout=120)
            hl = svc.host_loader
            deadline = time.monotonic() + 30
            while hl.stats.items_total < 10:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            svc.kill_node("node1")
            assert h.result() == [2 * i for i in range(n)]
            events = svc.telemetry.events_since(0)
    [record] = caplog.records
    msg = record.getMessage()
    assert msg.startswith("node node1 declared dead: ")
    assert " s since its last heartbeat, " in msg
    assert " items in flight, process " in msg
    assert msg.endswith(("still running", "exited"))
    [failure] = [e for e in events if e["kind"] == "failure"]
    assert failure["node"] == "node1"
    assert failure["items_in_flight"] >= 0
    assert failure["process_running"] in (True, False)


@pytest.mark.parametrize("visible,chip", [
    ("", None), ("2", 2), ("0,1", None)])
def test_a_node_reports_the_chip_it_was_bound_to(visible, chip):
    from repro.cluster.node_loader import bound_chip

    env = {"TPU_VISIBLE_CHIPS": visible} if visible else {}
    assert bound_chip(env) == chip


def test_the_pool_snapshot_shows_each_nodes_chip():
    with _service() as svc:
        svc.submit(_spec(_double, 8), timeout=60).result()
        deadline = time.monotonic() + 10
        while True:
            nodes = svc.metrics_snapshot()["nodes"]
            reports = [n.get("report") or {} for n in nodes.values()]
            if len(reports) == 2 and all("chip" in r for r in reports):
                break
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert [r["chip"] for r in reports] == [None, None]
