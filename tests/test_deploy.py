"""The pluggable deployment layer (repro.cluster.deploy) + placement policy.

Launcher-logic and policy tests run node-loaders as *threads*
(InProcessLauncher) — the full wire protocol over real localhost sockets,
none of the per-scenario interpreter-fork cost.  The SSHLauncher tests use
a stub ``ssh`` executable that runs the remote command locally, so the
whole fan-out path (command assembly, env export, code sync, handle
lifecycle, logs) is exercised hermetically; CI's ssh-smoke job runs the
same launcher against a real loopback sshd.
"""

import os
import socket
import stat
import subprocess
import sys
import threading
import time

import pytest

from repro.cluster.deploy import (
    InProcessLauncher,
    LocalLauncher,
    PlacementPolicy,
    SSHLauncher,
)
from repro.cluster.deploy.base import NodeHandle
from repro.cluster.deploy.local import (
    DEFAULT_COMPILE_CACHE,
    TPU_PROCESS_PORT_BASE,
    _child_env,
    compile_cache_dir,
    jax_node_env,
    tpu_chip_count,
    tpu_chip_env,
)
from repro.cluster.membership import (
    DONE,
    LAUNCHING,
    REGISTERED,
    REPLACED,
    Membership,
)
from repro.cluster.node_loader import connect_with_retry
from repro.cluster.service import ClusterService
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


class DeadHandle(NodeHandle):
    """A launch some machine swallowed: accepted, never came up."""

    def __init__(self, node_id):
        self.node_id = node_id
        self.where = "void"

    def poll(self):
        return 1

    def wait(self, timeout=None):
        return 1

    def kill(self):
        pass

    def logs(self):
        return []


class FlakyLauncher(InProcessLauncher):
    """Silently drops the first launch of the named nodes (they never dial
    the host) — the idle-workstation pool's classic failure mode."""

    def __init__(self, drop_first=(), **kw):
        super().__init__(**kw)
        self._drop = set(drop_first)
        self.dropped = []

    def launch(self, node_id, *, avoid=()):
        if node_id in self._drop:
            self._drop.discard(node_id)
            self.dropped.append(node_id)
            self.launched.append(node_id)
            return DeadHandle(node_id)
        return super().launch(node_id, avoid=avoid)


# ---------------------------------------------------------------------------
# membership states
# ---------------------------------------------------------------------------


def test_membership_launch_register_replace_lifecycle():
    m = Membership(HeartbeatMonitor())
    rec = m.expect("node0", now=0.0)
    assert rec.state == LAUNCHING and not rec.alive
    # An announced launch neither counts as arrived nor blocks termination.
    assert m.arrived_count() == 0
    assert m.finished()
    with pytest.raises(ValueError):
        m.expect("node0")

    # Respawn: retire the silent launch, announce its replacement.
    m.replace("node0")
    assert m.nodes["node0"].state == REPLACED
    m.expect("node0r2", now=1.0).attempts = 2
    m.register("node0r2", "127.0.0.1:5", now=1.5)
    assert m.nodes["node0r2"].state == REGISTERED
    assert m.arrived_count() == 1

    # The replaced original showing up late is still a usable worker.
    m.register("node0", "127.0.0.1:6", now=2.0)
    assert m.nodes["node0"].state == REGISTERED
    assert m.arrived_count() == 2
    # ...but a duplicate of a live member is rejected.
    with pytest.raises(ValueError):
        m.register("node0r2", "127.0.0.1:7")
    with pytest.raises(ValueError):
        m.replace("node0")

    m.mark_done("node0")
    m.mark_done("node0r2")
    assert m.finished()


def test_placement_policy_validation():
    PlacementPolicy().validate(3)
    PlacementPolicy(min_nodes=1, max_respawns=2).validate(3)
    with pytest.raises(ValueError, match="min_nodes"):
        PlacementPolicy(min_nodes=0).validate(3)
    with pytest.raises(ValueError, match="min_nodes"):
        PlacementPolicy(min_nodes=4).validate(3)
    with pytest.raises(ValueError, match="max_respawns"):
        PlacementPolicy(max_respawns=-1).validate(3)
    PlacementPolicy(max_heals=2).validate(3)
    with pytest.raises(ValueError, match="max_heals"):
        PlacementPolicy(max_heals=-1).validate(3)


# ---------------------------------------------------------------------------
# node-loader connect retry
# ---------------------------------------------------------------------------


def test_connect_retry_waits_for_late_listener():
    """A node-loader may start before the host is listening (uncontrolled
    remote start order): the dial must retry, not die on ECONNREFUSED."""
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()  # free the port: nobody is listening now

    got = {}

    def dial():
        try:
            sock = connect_with_retry("127.0.0.1", port, timeout=10.0)
            got["peer"] = sock.getpeername()
            sock.close()
        except OSError as exc:  # pragma: no cover - failure diagnostics
            got["error"] = exc

    t = threading.Thread(target=dial, daemon=True)
    t.start()
    time.sleep(0.6)  # let several refused attempts happen
    listener = socket.socket()
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("127.0.0.1", port))
    listener.listen(1)
    t.join(timeout=10)
    listener.close()
    assert not t.is_alive()
    assert got.get("peer") == ("127.0.0.1", port), got


def test_connect_retry_gives_up_after_timeout():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="could not reach"):
        connect_with_retry("127.0.0.1", port, timeout=0.5)
    assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# placement policy, end to end over the InProcessLauncher
# ---------------------------------------------------------------------------


def test_degraded_start_admits_job_with_min_nodes():
    """One launch is swallowed; min_nodes=1 admits the job with the
    survivor instead of raising at the registration barrier."""
    launcher = FlakyLauncher(drop_first=["node1"], connect_timeout=5.0)
    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(2, 2, 30, lambda x: x * x), backend="cluster",
        launcher=launcher, min_nodes=1, register_timeout=0.6,
        job_timeout=60.0, **FAST,
    )
    assert app.run() == sum(i * i for i in range(30))
    hl = app.host_loader
    assert hl.stats.degraded_start
    assert hl.stats.items_total == 30
    assert hl.membership.nodes["node0"].state == DONE
    # The straggler stays LAUNCHING — still eligible to late-join a longer
    # job — and never blocked termination.
    assert hl.membership.nodes["node1"].state == LAUNCHING
    assert app.orphaned() == []


def test_silent_node_is_respawned_and_job_runs_at_full_strength():
    """A node that never registers is relaunched (up to max_respawns): the
    job starts at full strength with the replacement doing real work."""
    launcher = FlakyLauncher(drop_first=["node1"], connect_timeout=5.0)
    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(2, 1, 40, lambda x: 3 * x), backend="cluster",
        launcher=launcher, max_respawns=1, respawn_after=0.3,
        register_timeout=10.0, job_timeout=60.0, **FAST,
    )
    assert app.run() == sum(3 * i for i in range(40))
    hl = app.host_loader
    assert hl.stats.respawns == 1
    assert not hl.stats.degraded_start
    assert hl.membership.nodes["node1"].state == REPLACED
    assert hl.membership.nodes["node1r2"].state == DONE
    assert hl.membership.nodes["node1r2"].attempts == 2
    # The replacement was a genuine worker, not a bystander.
    assert hl.membership.nodes["node1r2"].items_done > 0
    assert launcher.launched == ["node0", "node1", "node1r2"]
    assert app.orphaned() == []


def test_late_join_mid_run_gets_load_and_credits_exactly_once():
    """A node registering after the run started is admitted, shipped LOAD,
    and answered credits immediately; results stay exactly-once."""
    n_items = 40
    launcher = InProcessLauncher(connect_timeout=10.0,
                                 delays={"node1": 0.9})
    builder = ClusterBuilder()

    def work(x):
        time.sleep(0.05)
        return x + 1

    app = builder.build_application(
        _spec(2, 1, n_items, work), backend="cluster",
        launcher=launcher, min_nodes=1, register_timeout=0.3,
        job_timeout=60.0, **FAST,
    )
    assert app.run() == sum(i + 1 for i in range(n_items))
    hl = app.host_loader
    assert hl.stats.degraded_start  # node1 missed the barrier...
    assert hl.stats.late_joins == 1  # ...then joined mid-run
    assert hl.stats.items_total == n_items
    assert hl.stats.duplicates_dropped == 0
    assert hl.membership.nodes["node1"].state == DONE
    assert hl.membership.nodes["node1"].items_done > 0
    assert app.orphaned() == []


def test_slow_launcher_prepare_does_not_trigger_spurious_respawns():
    """The silence clock must start when the barrier does, not when the
    launches were announced: a launcher whose prepare() (code sync) takes
    longer than respawn_after must not get its healthy, just-launched
    nodes respawned out from under it."""

    class SlowPrepare(InProcessLauncher):
        def prepare(self, connect_host, port):
            time.sleep(0.6)  # a code sync slower than respawn_after
            super().prepare(connect_host, port)

    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(2, 1, 20, lambda x: x), backend="cluster",
        launcher=SlowPrepare(connect_timeout=10.0),
        max_respawns=2, respawn_after=0.25, register_timeout=10.0,
        job_timeout=60.0, **FAST,
    )
    assert app.run() == sum(range(20))
    assert app.host_loader.stats.respawns == 0
    assert app.orphaned() == []


def test_strict_barrier_still_raises_without_policy_relaxation():
    """The seed contract survives: no min_nodes / respawns -> a missing
    node fails the barrier with a TimeoutError."""
    launcher = FlakyLauncher(drop_first=["node1"], connect_timeout=5.0)
    app = ClusterBuilder().build_application(
        _spec(2, 1, 10, lambda x: x), backend="cluster",
        launcher=launcher, register_timeout=0.5, job_timeout=30.0, **FAST,
    )
    with pytest.raises(TimeoutError, match="registered"):
        app.run()
    assert app.orphaned() == []


# ---------------------------------------------------------------------------
# orphan hygiene
# ---------------------------------------------------------------------------


def test_start_failure_midway_reaps_already_launched_nodes():
    """If bootstrap raises after some launches (the orphaned-children leak),
    teardown still runs and reaps them."""

    class ExplodingLauncher(InProcessLauncher):
        def launch(self, node_id, *, avoid=()):
            if node_id == "node1":
                raise RuntimeError("fan-out exploded on node1")
            return super().launch(node_id, avoid=avoid)

    app = ClusterBuilder().build_application(
        _spec(2, 1, 10, lambda x: x), backend="cluster",
        launcher=ExplodingLauncher(connect_timeout=1.0),
        job_timeout=30.0, shutdown_grace=5.0, **FAST,
    )
    with pytest.raises(RuntimeError, match="fan-out exploded"):
        app.run()
    assert app.error is None  # raised synchronously, not via run_async
    assert "node0" in app.handles
    assert app.orphaned() == []


def test_launcher_and_hosts_are_mutually_exclusive():
    app = ClusterBuilder().build_application(
        _spec(1, 1, 1, lambda x: x), backend="cluster",
        launcher=InProcessLauncher(), hosts=["localhost"],
    )
    with pytest.raises(TypeError, match="not both"):
        app.start()


# ---------------------------------------------------------------------------
# SSHLauncher (hermetic: stub ssh executes the remote command locally)
# ---------------------------------------------------------------------------


@pytest.fixture
def stub_ssh(tmp_path):
    """An ``ssh`` stand-in: drops the hostname, runs the command locally."""
    path = tmp_path / "stub-ssh"
    path.write_text("#!/bin/sh\n# stub ssh: argv = <host> <command>\n"
                    "shift\nexec sh -c \"$1\"\n")
    path.chmod(path.stat().st_mode | stat.S_IXUSR)
    return str(path)


def test_ssh_launcher_runs_cluster_through_stub_ssh(stub_ssh):
    """The full fan-out path — command assembly, env export, per-node ssh
    process, logs — against a stub ssh (CI's ssh-smoke job runs the same
    launcher against a real loopback sshd)."""
    launcher = SSHLauncher(
        ["ws-a", "ws-b"], ssh_cmd=(stub_ssh,), ssh_opts=(),
        python=sys.executable, connect_timeout=30.0,
    )
    builder = ClusterBuilder()
    app = builder.build_application(
        _spec(2, 2, 30, lambda x: x * x), backend="cluster",
        launcher=launcher, job_timeout=120.0, **FAST,
    )
    assert app.run() == sum(i * i for i in range(30))
    # Round-robin placement over the host pool, one ssh client per node.
    assert {h.where for h in app.handles.values()} == {"ssh:ws-a", "ssh:ws-b"}
    assert all(h.returncode == 0 for h in app.handles.values())
    assert any("node-loader done" in line
               for h in app.handles.values() for line in h.logs())
    assert app.orphaned() == []


def test_ssh_code_sync_tar_fallback_ships_src_tree(tmp_path, stub_ssh):
    """Without rsync the sync falls back to tar-over-ssh; the remote dir
    ends up with the src tree the node-loader needs."""
    remote_dir = tmp_path / "deployed"
    launcher = SSHLauncher(
        ["ws-a"], ssh_cmd=(stub_ssh,), ssh_opts=(),
        remote_dir=str(remote_dir), sync="tar",
    )
    launcher.prepare("127.0.0.1", 2000)
    assert launcher.synced_hosts == ["ws-a"]
    synced = remote_dir / "src" / "repro" / "cluster" / "node_loader.py"
    assert synced.is_file()
    assert not list(remote_dir.glob("**/__pycache__"))
    # The launch command runs from the synced tree, not this checkout.
    cmd = launcher.remote_command("node0")
    assert f"cd {remote_dir}" in cmd
    assert f"PYTHONPATH={remote_dir}/src" in cmd


def test_ssh_launcher_end_to_end_from_synced_tree(tmp_path, stub_ssh):
    """Code sync + launch together: the node-loader actually executes out
    of the tar-synced copy (the plain-pickle / compile_cache_dir story)."""
    remote_dir = tmp_path / "deployed"
    launcher = SSHLauncher(
        ["ws-a"], ssh_cmd=(stub_ssh,), ssh_opts=(),
        remote_dir=str(remote_dir), sync="tar",
        python=sys.executable, connect_timeout=30.0,
    )
    app = ClusterBuilder().build_application(
        _spec(1, 2, 20, lambda x: 2 * x), backend="cluster",
        launcher=launcher, job_timeout=120.0, **FAST,
    )
    assert app.run() == sum(2 * i for i in range(20))
    assert app.orphaned() == []


def test_ssh_respawn_avoids_the_machine_that_swallowed_the_launch(stub_ssh,
                                                                  tmp_path):
    """Respawn placement: the replacement launch steers clear of the host
    whose first launch went silent."""
    # A second "ssh" that eats the command: the remote machine accepts the
    # session but the node-loader never comes up.
    eater = tmp_path / "eating-ssh"
    eater.write_text("#!/bin/sh\nexit 0\n")
    eater.chmod(eater.stat().st_mode | stat.S_IXUSR)

    class FirstLaunchEaten(SSHLauncher):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.eaten = False

        def launch(self, node_id, *, avoid=()):
            real_cmd = self.ssh_cmd
            if not self.eaten:
                self.eaten = True
                self.ssh_cmd = (str(eater),)
            try:
                return super().launch(node_id, avoid=avoid)
            finally:
                self.ssh_cmd = real_cmd

    launcher = FirstLaunchEaten(
        ["ws-bad", "ws-good"], ssh_cmd=(stub_ssh,), ssh_opts=(),
        python=sys.executable, connect_timeout=30.0,
    )
    app = ClusterBuilder().build_application(
        _spec(1, 1, 20, lambda x: x + 7), backend="cluster",
        launcher=launcher, max_respawns=1, respawn_after=0.4,
        register_timeout=15.0, job_timeout=120.0, **FAST,
    )
    assert app.run() == sum(i + 7 for i in range(20))
    hl = app.host_loader
    assert hl.stats.respawns == 1
    # node0 went to ws-bad and vanished; node0r2 avoided ws-bad.
    assert app.handles["node0"].where == "ssh:ws-bad"
    assert app.handles["node0r2"].where == "ssh:ws-good"
    assert app.orphaned() == []


def test_ssh_home_relative_remote_dir_stays_shell_expandable():
    """remote_dir='~/x' must reach the remote shell as "$HOME"/x — quoting
    the tilde would make cd/PYTHONPATH point at a literal './~' dir."""
    launcher = SSHLauncher(["ws"], remote_dir="~/cluster-app", sync="none")
    launcher.prepare("0.0.0.0", 2000)
    cmd = launcher.remote_command("node0")
    assert 'cd "$HOME"/cluster-app' in cmd
    assert 'PYTHONPATH="$HOME"/cluster-app/src' in cmd
    assert "'~" not in cmd


def test_ssh_explicit_connect_host_survives_prepare():
    """The quickstart shape: host binds 0.0.0.0, launcher carries the
    LAN-reachable address remote nodes must dial — prepare() must not
    clobber it with the (unroutable or loopback) bind address."""
    launcher = SSHLauncher(["ws"], connect_host="10.0.0.5")
    launcher.prepare("0.0.0.0", 2000)
    assert launcher.connect_host == "10.0.0.5"
    assert "--host 10.0.0.5" in launcher.remote_command("node0")
    # Unconfigured -> fall back to the bind address, loopback-resolved
    # (the ssh-to-localhost case).
    fallback = SSHLauncher(["ws"])
    fallback.prepare("0.0.0.0", 2000)
    assert fallback.connect_host == "127.0.0.1"


def test_local_launcher_is_the_default_and_unchanged():
    """No launcher option -> LocalLauncher subprocesses (seed behaviour)."""
    app = ClusterBuilder().build_application(
        _spec(1, 1, 10, lambda x: x), backend="cluster",
        job_timeout=60.0, **FAST,
    )
    assert app.run() == sum(range(10))
    assert isinstance(app.launcher, LocalLauncher)
    assert all(h.where == "local" for h in app.handles.values())
    assert app.orphaned() == []


def test_jax_node_env_sets_no_platform(monkeypatch):
    """A node's platform comes from whoever launches it: the overlay, the
    local child env and the ssh exports add no JAX_PLATFORMS."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax_node_env() == {}
    assert "JAX_PLATFORMS" not in jax_node_env("/cache")
    assert "JAX_PLATFORMS" not in _child_env("/cache")
    assert "JAX_PLATFORMS" not in SSHLauncher(["ws"])._remote_env()
    # The caller's own platform still reaches a local child untouched.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert _child_env()["JAX_PLATFORMS"] == "cpu"


def test_env_compile_cache_dir_wins_over_compile_cache_dir(monkeypatch,
                                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/env/cache")
    assert compile_cache_dir() == "/env/cache"
    assert jax_node_env("/arg/cache")["JAX_COMPILATION_CACHE_DIR"] \
        == "/env/cache"
    assert _child_env("/arg/cache")["JAX_COMPILATION_CACHE_DIR"] \
        == "/env/cache"
    assert SSHLauncher(["ws"], compile_cache_dir="/arg/cache")._remote_env()[
        "JAX_COMPILATION_CACHE_DIR"] == "/env/cache"
    # Unset: the argument, and by default one fixed path in the checkout,
    # whatever the working directory.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir(tmp_path)
    assert _child_env("/arg/cache")["JAX_COMPILATION_CACHE_DIR"] \
        == "/arg/cache"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache_dir() == DEFAULT_COMPILE_CACHE \
        == os.path.join(root, "results", "xla_cache")


# ---------------------------------------------------------------------------
# chip binding: one TPU chip a node in a pool of several
# ---------------------------------------------------------------------------

BINDING = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
           "TPU_PROCESS_BOUNDS", "TPU_PROCESS_PORT",
           "ALLOW_MULTIPLE_LIBTPU_LOAD")


@pytest.mark.parametrize("slot", [0, 1, 2, 3])
def test_each_slot_of_a_four_chip_host_sees_its_own_chip(slot):
    env = tpu_chip_env(slot, 4, {})
    assert env == {
        "TPU_VISIBLE_CHIPS": str(slot),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(TPU_PROCESS_PORT_BASE + slot),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    others = [tpu_chip_env(s, 4, {}) for s in range(4) if s != slot]
    for key in ("TPU_VISIBLE_CHIPS", "TPU_PROCESS_PORT"):
        assert env[key] not in {o[key] for o in others}


@pytest.mark.parametrize("chips,caller", [
    (0, {}),                                # no TPU on this machine
    (1, {}),                                # one chip: the node owns it
    (4, {"JAX_PLATFORMS": "cpu"}),          # the caller keeps JAX on the CPU
    (4, {"TPU_VISIBLE_CHIPS": "2,3"}),      # the caller chose the chips
], ids=["no_chips", "one_chip", "jax_on_cpu", "caller_chips"])
def test_no_binding_applies(chips, caller):
    assert tpu_chip_env(0, chips, caller) == {}
    assert tpu_chip_env(1, chips, caller) == {}


@pytest.mark.parametrize("slot", [4, 5])
def test_a_node_beyond_the_chips_runs_on_the_cpu(slot):
    assert tpu_chip_env(slot, 4, {}) == {"JAX_PLATFORMS": "cpu"}


@pytest.mark.parametrize("files,chips", [
    (["accel0", "accel1", "accel2", "accel3"], 4),    # TPU v4 and earlier
    (["vfio/vfio", "vfio/0", "vfio/1", "vfio/2", "vfio/3"], 4),  # v5e
    (["vfio/vfio", "vfio/0"], 1),
    (["null", "vfio/vfio"], 0),
    ([], 0),
], ids=["accel", "vfio", "vfio_one", "vfio_control_only", "none"])
def test_the_launcher_counts_chips_from_device_files(tmp_path, files, chips):
    for name in files:
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).touch()
    assert tpu_chip_count(str(tmp_path)) == chips


class FakeProc:
    """A node-loader process that records what happens to it: ``events``
    gets ("kill", id) and ("wait", id); it exits when killed unless
    ``stuck``, and ``exit()`` ends it as a crash would."""

    def __init__(self, node_id, events, env):
        self.node_id, self.events, self.env = node_id, events, env
        self.stdout = self.stderr = None
        self.returncode = None
        self.stuck = False

    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        self.events.append(("wait", self.node_id))
        if self.returncode is None:
            raise subprocess.TimeoutExpired("node", timeout)
        return self.returncode

    def kill(self):
        self.events.append(("kill", self.node_id))
        if not self.stuck:
            self.returncode = -9

    def exit(self):
        self.returncode = 1


@pytest.fixture
def fake_host(monkeypatch):
    """A host of four TPU chips whose node-loaders are :class:`FakeProc`:
    node id -> its process (the latest of that id), and the events."""
    from repro.cluster.deploy import local

    procs, events = {}, []

    def spawn(host, port, node_id, *, python, preload, compile_cache_dir,
              env):
        events.append(("spawn", node_id))
        procs[node_id] = FakeProc(node_id, events,
                                  local._child_env(compile_cache_dir, env))
        return procs[node_id]

    for key in BINDING + ("JAX_PLATFORMS",):
        monkeypatch.delenv(key, raising=False)
    monkeypatch.setattr(local, "tpu_chip_count", lambda dev="/dev": 4)
    monkeypatch.setattr(local, "spawn_node_loader", spawn)
    return procs, events


def _pool(nodes):
    """A service on the fake host with its ``nodes`` launched, not
    started: the launcher's and ``_relaunch``'s part alone."""
    svc = ClusterService(nodes=nodes, launcher=LocalLauncher())
    svc.launcher.plan(nodes)
    svc.launcher.prepare("127.0.0.1", 2000)
    for i in range(nodes):
        svc.handles[f"node{i}"] = svc.launcher.launch(f"node{i}")
    return svc


def _live_chips(svc):
    return sorted(h.chip for h in svc.handles.values() if h.poll() is None)


def test_a_pool_of_four_binds_node_k_to_chip_k(fake_host):
    procs, _ = fake_host
    svc = _pool(4)
    for i in range(4):
        assert svc.handles[f"node{i}"].chip == i
        env = procs[f"node{i}"].env
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        assert env["TPU_PROCESS_PORT"] == str(TPU_PROCESS_PORT_BASE + i)
        assert "JAX_PLATFORMS" not in env


def test_a_pool_of_one_node_is_launched_without_any_binding(fake_host):
    procs, _ = fake_host
    svc = _pool(1)
    assert svc.handles["node0"].chip is None
    assert not set(BINDING + ("JAX_PLATFORMS",)) & set(procs["node0"].env)


def test_nodes_beyond_the_chips_run_on_the_cpu_and_are_logged_once(
        fake_host, caplog):
    procs, _ = fake_host
    with caplog.at_level("WARNING", logger="repro.cluster.deploy.local"):
        svc = _pool(6)
    assert [svc.handles[f"node{i}"].chip for i in range(4)] == [0, 1, 2, 3]
    for nid in ("node4", "node5"):
        assert svc.handles[nid].chip is None
        assert procs[nid].env["JAX_PLATFORMS"] == "cpu"
        assert "TPU_VISIBLE_CHIPS" not in procs[nid].env
    [record] = caplog.records
    assert "node4 and later nodes run on the CPU" in record.getMessage()


def test_a_relaunch_takes_the_dead_nodes_chip_after_its_handle_exited(
        fake_host):
    procs, events = fake_host
    svc = _pool(4)
    old = procs["node1"]
    del events[:]
    assert svc._relaunch("node1", "node1r2")
    # The old node is killed and waited for before its replacement starts.
    assert events == [("kill", "node1"), ("wait", "node1"),
                      ("spawn", "node1r2")]
    assert old.returncode is not None
    assert svc.handles["node1r2"].chip == 1
    assert procs["node1r2"].env["TPU_VISIBLE_CHIPS"] == "1"


def test_a_relaunch_never_shares_the_chip_of_a_node_that_did_not_exit(
        fake_host):
    procs, events = fake_host
    svc = _pool(4)
    procs["node2"].stuck = True  # survives its kill past the wait
    assert svc._relaunch("node2", "node2r2")
    assert ("wait", "node2") in events
    assert svc.handles["node2r2"].chip is None
    assert procs["node2r2"].env["JAX_PLATFORMS"] == "cpu"
    # Once it has exited, the next launch gets the chip.
    procs["node2"].returncode = -9
    assert svc.launcher.launch("node4").chip == 2


def test_a_healing_pool_keeps_one_chip_a_node(fake_host):
    procs, _ = fake_host
    svc = _pool(4)
    for dead, new in [("node3", "node3r2"), ("node0", "node0r2"),
                      ("node3r2", "node3r3")]:
        procs[dead].exit()  # it crashed: the host healed it
        assert svc._relaunch(dead, new)
        assert _live_chips(svc) == [0, 1, 2, 3]
    assert svc.handles["node0r2"].chip == 0
    assert svc.handles["node3r3"].chip == 3


def test_an_unbound_relaunch_starts_the_replacement_first(fake_host,
                                                          monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    procs, events = fake_host
    svc = _pool(2)
    del events[:]
    assert svc._relaunch("node1", "node1r2")
    assert events == [("spawn", "node1r2"), ("kill", "node1")]
    assert svc.handles["node1r2"].chip is None


def test_racing_launches_never_share_a_chip(fake_host):
    """Boot, heals and growth launch from different threads: under racing
    launches each chip still goes to one node, the rest to the CPU."""
    launcher = LocalLauncher()
    launcher.plan(4)
    launcher.prepare("127.0.0.1", 2000)
    handles = []
    start = threading.Barrier(os.cpu_count() + 5)

    def launch(i):
        start.wait(timeout=10)
        handles.append(launcher.launch(f"node{i}"))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=launch, args=(i,))
                   for i in range(start.parties)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    chips = sorted(h.chip for h in handles if h.chip is not None)
    assert chips == [0, 1, 2, 3] and len(handles) == start.parties
