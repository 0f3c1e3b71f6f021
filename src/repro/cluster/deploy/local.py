"""LocalLauncher: node-loaders as subprocesses of this machine.

The paper's §6.1 workflow — "operation and testing of a system can be
conducted on a single host node before using multiple nodes" — with true
process isolation: each Node-Loader is a fresh ``python -m
repro.cluster.node_loader`` OS process talking TCP on localhost, so there is
no GIL coupling and killing one is a *real* node death, not an injected one.

The launcher exports the host's ``sys.path`` to the children so code shipped
by reference (plain-pickle fallback, user modules) resolves; code shipped by
value (cloudpickle closures) needs only the libraries it imports.
"""

from __future__ import annotations

import collections
import os
import pathlib
import subprocess
import sys
import threading
from typing import Sequence

from repro.cluster.deploy.base import Launcher, NodeHandle


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


def _child_env(compile_cache_dir: str | None = None) -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
    for key, val in jax_node_env(compile_cache_dir).items():
        env.setdefault(key, val)  # respect the caller's environment
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
                      compile_cache_dir: str | None = None
                      ) -> subprocess.Popen:
    """Start one Node-Loader subprocess (kept for direct callers).

    ``preload`` names modules the child imports concurrently with its
    registration (e.g. ``("jax.numpy",)``), so heavy environment boot
    overlaps the load-network handshake instead of serializing after it.
    """
    return subprocess.Popen(
        node_loader_argv(host, port, node_id, python=python, preload=preload),
        env=_child_env(compile_cache_dir),
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
    extracted out of ``ProcessClusterApplication``)."""

    def __init__(self, *, python: str = sys.executable,
                 preload: Sequence[str] = (),
                 compile_cache_dir: str | None = None):
        self.python = python
        self.preload = tuple(preload)
        self.compile_cache_dir = compile_cache_dir
        self.connect_host = "127.0.0.1"
        self.port = 0

    def launch(self, node_id: str, *,
               avoid: Sequence[str] = ()) -> PopenNodeHandle:
        proc = spawn_node_loader(
            self.connect_host, self.port, node_id,
            python=self.python, preload=self.preload,
            compile_cache_dir=self.compile_cache_dir,
        )
        return PopenNodeHandle(node_id, proc, where="local")
