"""Control jobs the benchmark sends to the node-loaders that hold the chips.

Only a node may touch its chip while the pool is up, so whatever the
benchmark needs from the device side runs there as a job of its own: a
report of the devices, the start and stop of a profiler trace, the peak
of device memory, and the garbage collector's long pauses.  Each runs
once in every node of the pool, in one job (:func:`every_node_spec`).
Each work function is a module-level function of this file, which the
node imports (the launcher hands it the benchmark's ``sys.path``).
"""

from __future__ import annotations

import functools
import gc
import os
import pathlib
import sys
import threading
import time
import uuid
from typing import Any, Callable

# Collections that take at least this long are kept, with their end on the
# monotonic clock (one clock for every process of the machine).
GC_PAUSE_S = 0.02
_gc: dict[str, Any] = {"start": None, "pauses": []}


def one_item_spec(work: Callable[[Any], Any], item: Any):
    """A job of one item, ``item``, whose result is ``work(item)``."""
    from repro.core.dsl import ClusterSpec
    from repro.core.processes import EmitDetails, ResultDetails

    def create(sent):
        return (None, sent) if sent else (item, True)

    return ClusterSpec.simple(
        host="127.0.0.1", nclusters=1, workers_per_node=1,
        emit_details=EmitDetails(name="probe", init=lambda: False,
                                 create=create),
        work_function=work,
        result_details=ResultDetails(name="probe", init=lambda: None,
                                     collect=lambda _acc, r: r),
    )


# How long an item of an every-node job waits for the job to reach every
# node before it fails.
MEET_S = 120.0
# In each node, the answer of every every-node job it has served, by token.
_answers: dict[str, Any] = {}
_answers_lock = threading.Lock()


def every_node_spec(work: Callable[[Any], Any], args: dict[str, Any],
                    workers: int, meet_dir: str):
    """A job that runs ``work(args[node])`` once in each node named in
    ``args``, a pool of ``workers`` workers a node, all at about one moment.

    The job has ``workers`` items a node, and no node holds more of them
    than it has workers (the stage's prefetch is 0).  Each item first marks
    its node in ``meet_dir`` and waits until every node is marked: a node
    whose workers all wait takes no more items, so the rest reach the
    others.  Then the node's first item runs the work, once for the job's
    token, and every item answers ``(node id, that answer)``.  The
    collected answer is a list of them."""
    from repro.core.dsl import PipelineSpec, Stage
    from repro.core.processes import EmitDetails, ResultDetails

    items = len(args) * workers

    def create(i):
        return (None, i) if i >= items else (i, i + 1)

    serve = functools.partial(serve_once, uuid.uuid4().hex, work, args,
                              meet_dir)
    return PipelineSpec.simple(
        host="127.0.0.1",
        emit_details=EmitDetails(name="probe", init=lambda: 0,
                                 create=create),
        stages=[Stage(name="probe", fn=serve, prefetch=0)],
        result_details=ResultDetails(name="probe", init=list,
                                     collect=lambda acc, r: [*acc, r]),
    )


def node_id() -> str:
    """This node-loader's id, from the command line that every launcher
    gives it (``--node-id``)."""
    return sys.argv[sys.argv.index("--node-id") + 1]


def serve_once(token: str, work: Callable[[Any], Any], args: dict[str, Any],
               meet_dir: str, _item) -> tuple[str, Any]:
    """One item of an every-node job, in the node that took it."""
    node = node_id()
    pathlib.Path(meet_dir, node).touch()
    deadline = time.monotonic() + MEET_S
    while len(os.listdir(meet_dir)) < len(args):
        if time.monotonic() > deadline:
            raise TimeoutError(f"{sorted(os.listdir(meet_dir))} of "
                               f"{sorted(args)} met in {MEET_S} s")
        time.sleep(0.001)
    with _answers_lock:
        if token not in _answers:
            _answers[token] = work(args[node])
        return node, _answers[token]


def device_report(_item) -> dict:
    """The devices as JAX reports them in the node, and its pid."""
    import os

    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "pid": os.getpid()}


def start_trace(log_dir: str) -> bool:
    """Start the profiler in this node, writing under ``log_dir``: device
    activity and the runtime's host events, without the Python tracer (it
    would slow every call of the band program it is meant to observe)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    return True


def stop_trace(_item) -> bool:
    import jax

    jax.profiler.stop_trace()
    return True


def memory_peak(_item) -> int | None:
    """The highest ``peak_bytes_in_use`` over this node's devices, or None
    where the backend keeps no such statistic."""
    import jax

    peaks = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc["start"] = time.perf_counter()
    elif _gc["start"] is not None:
        took = time.perf_counter() - _gc["start"]
        if took >= GC_PAUSE_S:
            _gc["pauses"].append((time.monotonic(), info["generation"], took))


def watch_gc(_item) -> bool:
    """From now on keep, in this process, every collection of at least
    ``GC_PAUSE_S``."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return True


def gc_pauses(_item) -> list[tuple[float, int, float]]:
    """The long collections kept so far: (end, generation, seconds)."""
    return list(_gc["pauses"])
