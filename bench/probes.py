"""One-item control jobs the benchmark sends to the node that holds the chip.

Only the node may touch the chip while the pool is up, so whatever the
benchmark needs from the device side runs there as a job of its own: a
report of the devices, the start and stop of a profiler trace, the peak
of device memory, and the garbage collector's long pauses.  Each work
function is a module-level function of this file, which the node imports
(the launcher hands it the benchmark's ``sys.path``).
"""

from __future__ import annotations

import gc
import time
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
    """Start the profiler in the node: device activity and the runtime's
    host events, without the Python tracer (it would slow every call of
    the band program it is meant to observe)."""
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
    """The highest ``peak_bytes_in_use`` over the node's devices, or None
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
