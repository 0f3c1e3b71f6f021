"""LocalLauncher: node-loaders as subprocesses of this machine.

The paper's §6.1 workflow — "operation and testing of a system can be
conducted on a single host node before using multiple nodes" — with true
process isolation: each Node-Loader is a fresh ``python -m
repro.cluster.node_loader`` OS process talking TCP on localhost, so there is
no GIL coupling and killing one is a *real* node death, not an injected one.

The launcher exports the host's ``sys.path`` to the children so code shipped
by reference (plain-pickle fallback, user modules) resolves; code shipped by
value (cloudpickle closures) needs only the libraries it imports.

On a machine with several TPU chips, a pool of several nodes gets one chip
a node (:func:`tpu_chip_env`): each node-loader is then its own one-chip
TPU process, as a workstation of the paper is its own machine.
"""

from __future__ import annotations

import collections
import glob
import logging
import os
import pathlib
import subprocess
import sys
import threading
from typing import Mapping, Sequence

from repro.cluster.deploy.base import Launcher, NodeHandle

log = logging.getLogger(__name__)

# The first node's libtpu process port; node k takes the next k-th one.
TPU_PROCESS_PORT_BASE = 8476


# The compile cache's fixed home when JAX_COMPILATION_CACHE_DIR is unset:
# inside the checkout (gitignored), resolved from this file and not from
# the working directory, so every process of a run finds the same one.
DEFAULT_COMPILE_CACHE = str(
    pathlib.Path(__file__).resolve().parents[4] / "results" / "xla_cache")


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else the fixed
    :data:`DEFAULT_COMPILE_CACHE`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_COMPILE_CACHE


def jax_node_env(compile_cache_dir: str | None = None) -> dict[str, str]:
    """The env overlay every node-loader gets, whatever launches it.

    It sets no platform: a node runs on whatever ``JAX_PLATFORMS`` its
    launcher's environment holds (tests keep the CPU that way, a TPU host
    leaves it unset).  With a compile cache — the caller's
    ``JAX_COMPILATION_CACHE_DIR`` where it is set, else
    ``compile_cache_dir`` — every node-loader of the cluster reads and
    writes that one persistent XLA cache, so a node compiles a kernel for
    its own device once and later processes load the binary.  One recipe
    shared by every launcher (local subprocess env, ssh ``env`` exports),
    so a knob added here reaches remote nodes too.
    """
    env: dict[str, str] = {}
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or compile_cache_dir
    if cache:
        env["JAX_COMPILATION_CACHE_DIR"] = cache
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return env


def tpu_chip_count(dev: str = "/dev") -> int:
    """The TPU chips of this machine, from its device files (no JAX).

    A TPU host gives a process one device file a chip: ``/dev/accel<n>``
    on TPU v4 and earlier, ``/dev/vfio/<n>`` on TPU v5e and later, beside
    ``/dev/vfio/vfio``, the VFIO control file, which is no chip.  A TPU
    v5e 4-chip host (v5litepod-4) shows ``/dev/vfio/0`` to ``/dev/vfio/3``
    and ``/dev/vfio/vfio``, and no ``/dev/accel*``.
    """
    accel = glob.glob(os.path.join(dev, "accel[0-9]*"))
    if accel:
        return len(accel)
    vfio = os.path.join(dev, "vfio")
    if not os.path.isdir(vfio):
        return 0
    return sum(1 for name in os.listdir(vfio) if name.isdigit())


def tpu_chip_env(slot: int, chips: int,
                 env: Mapping[str, str]) -> dict[str, str]:
    """The env overlay that binds the node in ``slot`` of a pool to TPU
    chip ``slot`` of a machine with ``chips`` chips, given the caller's
    environment ``env``.

    Chip ``slot`` is the only one its libtpu sees, as a slice of one chip
    in one process with a port of its own, and the lock libtpu takes
    against a second process on the machine is lifted: the other nodes
    hold the other chips (JAX's own multi-process tests split a host this
    way).  No binding — an empty overlay — on a machine with fewer than two
    chips, or where the caller keeps JAX on the CPU or chose the visible
    chips itself.  A slot beyond the chips runs on the CPU.
    """
    if (chips < 2 or env.get("JAX_PLATFORMS") == "cpu"
            or "TPU_VISIBLE_CHIPS" in env):
        return {}
    if slot >= chips:
        return {"JAX_PLATFORMS": "cpu"}
    return {
        "TPU_VISIBLE_CHIPS": str(slot),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(TPU_PROCESS_PORT_BASE + slot),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def _child_env(compile_cache_dir: str | None = None,
               overlay: Mapping[str, str] | None = None) -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
    for key, val in jax_node_env(compile_cache_dir).items():
        env.setdefault(key, val)  # respect the caller's environment
    env.update(overlay or {})
    return env


def node_loader_argv(host: str, port: int, node_id: str,
                     *, python: str = sys.executable,
                     preload: Sequence[str] = (),
                     connect_timeout: float | None = None) -> list[str]:
    """The §4 'identical executable' invocation every launcher fans out."""
    cmd = [python, "-m", "repro.cluster.node_loader",
           "--host", host, "--port", str(port), "--node-id", node_id]
    if preload:
        cmd += ["--preload", ",".join(preload)]
    if connect_timeout is not None:
        cmd += ["--connect-timeout", str(connect_timeout)]
    return cmd


def spawn_node_loader(host: str, port: int, node_id: str,
                      *, python: str = sys.executable,
                      preload: tuple[str, ...] = (),
                      compile_cache_dir: str | None = None,
                      env: Mapping[str, str] | None = None
                      ) -> subprocess.Popen:
    """Start one Node-Loader subprocess (kept for direct callers).

    ``preload`` names modules the child imports concurrently with its
    registration (e.g. ``("jax.numpy",)``), so heavy environment boot
    overlaps the load-network handshake instead of serializing after it.
    ``env`` is laid over the child's environment last (a chip binding).
    """
    return subprocess.Popen(
        node_loader_argv(host, port, node_id, python=python, preload=preload),
        env=_child_env(compile_cache_dir, env),
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


class PopenNodeHandle(NodeHandle):
    """A node-loader behind a local ``subprocess.Popen`` (direct child or an
    ssh client process).  Stdout+stderr are drained continuously so a chatty
    child never blocks on a full pipe; the tail is kept for diagnostics."""

    def __init__(self, node_id: str, proc: subprocess.Popen,
                 where: str = "local", log_lines: int = 200):
        self.node_id = node_id
        self.where = where
        self.proc = proc
        self._log: collections.deque[str] = collections.deque(maxlen=log_lines)
        self._drainers: list[threading.Thread] = []
        for stream in (proc.stdout, proc.stderr):
            if stream is None:
                continue
            t = threading.Thread(target=self._drain, args=(stream,),
                                 name=f"drain-{node_id}", daemon=True)
            t.start()
            self._drainers.append(t)

    def _drain(self, stream) -> None:
        for line in stream:
            self._log.append(line.rstrip("\n"))
        stream.close()

    def poll(self) -> int | None:
        return self.proc.poll()

    def wait(self, timeout: float | None = None) -> int | None:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None

    def kill(self) -> None:
        self.proc.kill()

    def logs(self) -> list[str]:
        return list(self._log)

    def join_drainers(self, timeout: float = 5.0) -> None:
        for t in self._drainers:  # EOF arrives once the child exits
            t.join(timeout=timeout)

    @property
    def returncode(self) -> int | None:
        return self.proc.returncode


class LocalLauncher(Launcher):
    """Forks node-loader subprocesses on this machine (the seed behaviour,
    extracted out of ``ProcessClusterApplication``).

    A pool of several nodes on a machine with several TPU chips binds each
    launch to the lowest chip no running node holds (:func:`tpu_chip_env`):
    node k of the boot gets chip k, a relaunch the chip its lost node
    released.  A pool of one node is launched without any binding and owns
    what it finds.
    """

    def __init__(self, *, python: str = sys.executable,
                 preload: Sequence[str] = (),
                 compile_cache_dir: str | None = None):
        self.python = python
        self.preload = tuple(preload)
        self.compile_cache_dir = compile_cache_dir
        self.connect_host = "127.0.0.1"
        self.port = 0
        self.chips = 0  # TPU chips to bind nodes to (plan() counts them)
        self._held: dict[int, NodeHandle] = {}  # chip -> its node
        self._lock = threading.Lock()  # boot, heals and growth launch
        self._told_cpu = False

    def plan(self, nodes: int) -> None:
        self.chips = tpu_chip_count() if nodes > 1 else 0

    def launch(self, node_id: str, *,
               avoid: Sequence[str] = ()) -> PopenNodeHandle:
        with self._lock:
            held = {c for c, h in self._held.items() if h.poll() is None}
            slot = min(set(range(len(held) + 1)) - held)
            bind = tpu_chip_env(slot, self.chips, os.environ)
            if bind.get("JAX_PLATFORMS") == "cpu" and not self._told_cpu:
                self._told_cpu = True
                log.warning("%d TPU chips, each held by a node: %s and "
                            "later nodes run on the CPU", self.chips, node_id)
            proc = spawn_node_loader(
                self.connect_host, self.port, node_id,
                python=self.python, preload=self.preload,
                compile_cache_dir=self.compile_cache_dir, env=bind,
            )
            handle = PopenNodeHandle(node_id, proc, where="local")
            if "TPU_VISIBLE_CHIPS" in bind:
                handle.chip = slot
                self._held[slot] = handle
            return handle
