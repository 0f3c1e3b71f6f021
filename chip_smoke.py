"""Smoke test: the paper-size Mandelbrot farm on one TPU, end to end.

Runs the paper's instance (§8: 3200 lines x 5600 points, escape value
1000, i.e. 800 bands of 4 lines and 17,920,000 points) through the
system's own entry points: a ``ClusterService`` pool, with its default
launcher and liveness settings, of one node-loader process with four
workers.  That node-loader is the one process that owns the chip: it
starts the TPU backend while it boots (its preload imports jax).  It
first takes a warm-up job, which reports its device and renders the
middle band with the Pallas kernel there (the kernel's compile), then
the farm twice: cold (the farm's code is shipped and its band program
compiles) and warm (the node rebinds it from its code cache).  Once the
pool has closed and its node process has exited, this process takes the
chip and checks, against the jnp oracle: both submissions' totals,
exactly; the warm-up's band point by point (the kernel at the farm's
band shape); and the compiled kernel over the whole grid point by point
(a second program, the grid in one call).

Earlier lines of standard output carry the figures; the last line is one
JSON object, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": 1}}``, with the device as the node that ran the kernel reports
it.  A failed phase, a mismatch, or a node that finds no TPU exits
non-zero without that line.

Run from the repository root (no arguments)::

    python chip_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

LINES = 3200
WIDTH = 5600
MAX_ITERS = 1000
WORKERS = 4
SUBMITS = 2  # cold, then warm
JOB_TIMEOUT_S = 600.0


class SmokeFailure(RuntimeError):
    """A phase of the smoke test failed; the message says which."""


def run_farm(*, lines: int, width: int, max_iters: int, interpret: bool,
             platform: str, workers: int = WORKERS, log=print) -> dict:
    """Run the farm through a one-node ``ClusterService`` and check it.

    The node must report ``platform``; this process touches JAX only after
    the pool has closed and its node has exited.  Returns the figures;
    raises :class:`SmokeFailure` on any failed check.
    """
    from repro.apps.mandelbrot_farm import (
        LINES_PER_ITEM,
        farm_grid,
        farm_spec,
        farm_totals,
        warm_spec,
    )
    from repro.cluster.deploy.local import compile_cache_dir
    from repro.cluster.service import ClusterService

    spec = farm_spec(1, workers, lines=lines, width=width,
                     max_iters=max_iters, interpret=interpret)
    band_line = (lines // LINES_PER_ITEM // 2) * LINES_PER_ITEM
    warm = warm_spec(width=width, max_iters=max_iters, interpret=interpret,
                     first_line=band_line, platform=platform)
    svc = ClusterService(nodes=1, workers=workers,
                         preload=("repro.kernels.mandelbrot.ops",))
    report: dict = {"submits": []}
    results = []
    try:
        with svc:
            t0 = time.perf_counter()
            handle = svc.submit(warm, timeout=JOB_TIMEOUT_S)
            device = handle.result()
            report["device"] = device
            report["pool_boot_ms"] = svc.boot_ms
            node = svc.metrics_snapshot()["nodes"]["node0"]["report"]
            report["node_boot_ms"] = node["boot_ms"]
            report["node_backend_ms"] = node["backend_ms"]
            log(f"warm: seconds={time.perf_counter() - t0:.3f} "
                f"pool_boot_ms={svc.boot_ms:.1f} "
                f"cluster_boot_ms={handle.stats()['cluster_boot_ms']:.1f} "
                f"node_pid={device['pid']} platform={device['platform']} "
                f"device_kind={device['device_kind']} "
                f"device_count={device['device_count']} "
                f"node_boot_ms={node['boot_ms']} "
                f"node_backend_start_ms={node['backend_ms']} "
                f"compile_ms(first call)={device['first_call_ms']}")
            if device["platform"] != platform:
                raise SmokeFailure(
                    f"the node runs on {device['platform']!r}, not "
                    f"{platform!r}: no accelerator")
            for i in range(SUBMITS):
                t0 = time.perf_counter()
                handle = svc.submit(spec, timeout=JOB_TIMEOUT_S)
                results.append(handle.result())
                seconds = time.perf_counter() - t0
                stats = handle.stats()
                sub = {
                    "seconds": seconds,
                    "cluster_boot_ms": stats["cluster_boot_ms"],
                    "submit_to_first_result_ms":
                        stats["submit_to_first_result_ms"],
                    "code_shipped": stats["code_shipped"],
                    "code_cached": stats["code_cached"],
                    "items_per_node": {nid: n["items"] for nid, n
                                       in stats["nodes"].items()},
                }
                report["submits"].append(sub)
                log(f"submit {i + 1} ({'cold' if i == 0 else 'warm'}): "
                    + " ".join(f"{k}={v}" for k, v in sub.items()))
    except BaseException:
        for nid, h in svc.handles.items():
            for line in h.logs()[-40:]:
                log(f"[{nid}] {line}", file=sys.stderr)
        raise
    finally:
        orphaned = svc.orphaned()
    if orphaned:
        raise SmokeFailure(f"node-loaders still running: {orphaned}")

    # The pool's node has exited: the chip is free for this process.
    import jax
    import numpy as np

    from repro.kernels.mandelbrot.ops import mandelbrot
    from repro.kernels.mandelbrot.ref import mandelbrot_reference

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    host_device = jax.devices()[0]
    if host_device.platform != platform:
        raise SmokeFailure(f"the oracle would run on {host_device.platform!r}")
    x0, y0 = farm_grid(lines, width)
    t0 = time.perf_counter()
    oracle_iters, oracle_colour = jax.jit(
        mandelbrot_reference, static_argnums=2)(x0, y0, max_iters)
    expected = farm_totals(oracle_iters, oracle_colour)
    report["oracle"] = {"iterations": expected[0], "white": expected[1],
                        "points": expected[2],
                        "seconds": time.perf_counter() - t0}
    log(f"oracle: {report['oracle']}")

    # The warm-up's band, rendered on the node by the kernel at the farm's
    # band shape, point by point.
    band = oracle_iters[band_line:band_line + LINES_PER_ITEM]
    report["band_points_differing"] = int(
        np.sum(device["band_iters"] != np.asarray(band)))
    log(f"band at line {band_line}: points where the node's kernel and "
        f"the oracle differ={report['band_points_differing']}")

    # The compiled kernel over the whole grid, point by point.
    t0 = time.perf_counter()
    kernel_iters, _ = mandelbrot(x0, y0, max_iters=max_iters,
                                 interpret=interpret)
    report["points_differing"] = int(
        np.sum(np.asarray(kernel_iters) != np.asarray(oracle_iters)))
    log(f"whole grid: kernel seconds (compile included)="
        f"{time.perf_counter() - t0:.3f} points where kernel and oracle "
        f"differ={report['points_differing']}")

    for i, got in enumerate(results):
        if tuple(got) != expected:
            raise SmokeFailure(
                f"submit {i + 1}: farm totals {tuple(got)} != oracle "
                f"{expected} (points differing: "
                f"{report['points_differing']})")
    for key in ("band_points_differing", "points_differing"):
        if report[key]:
            raise SmokeFailure(f"{key}={report[key]}: the kernel and the "
                               "oracle differ")
    return report


def main() -> int:
    try:
        report = run_farm(lines=LINES, width=WIDTH, max_iters=MAX_ITERS,
                          interpret=False, platform="tpu")
    except Exception as exc:  # every failed phase ends here: no result line
        print(f"chip_smoke failed: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return 1
    device = report["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"],
        "kind": device["device_kind"],
        "count": device["device_count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
