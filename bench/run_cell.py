"""Run one cell of the benchmark once and print its result line.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  The cell is an entry of ``BENCHMARK.json``'s
``workloads``; its configuration, traffic mix and metric readers are found
by name under ``bench/``.  The configuration's ``nodes`` node-loaders each
own the chips they find: one node on one chip, or one node per chip.
Set-up (``setup_s``) runs from this process's start to the window's first
timed submit: the pool's boot, the nodes' backend start, one band in
every node (which ships the code and compiles there), and two warm jobs
at the cell's band shape.  Then the traffic runs for ``--seconds``; the
job still running at the close is waited for, every node reports its
memory peak, and the pool closes.  Only then does this process touch JAX:
it runs the plain reference on the chip and judges every job's answer
(``bench/judge.py``).

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` every node traces a slice of the window and the metrics are
the cell's per-layer ones, with the devices' busy time and a breakdown.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``),
and last ``checks``, each number compared with its limit; the same
numbers close standard error.  No accelerator, too few chips, a node of
several that does not own exactly one chip, a device kind missing from
``bench/devices.json``, or an unknown workload: exit code 1 or 2 and no
result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    # Import the benchmark as the package ``bench`` and the program from
    # the checkout's ``src``; the launcher hands both paths to the node.
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def run(root: pathlib.Path, args: argparse.Namespace, *, t_start: float,
        platform: str | None = "tpu", device_kinds: dict | None = None,
        spec_factory=None, log=log) -> dict:
    """One run of the cell ``args.workload`` of ``root/BENCHMARK.json``:
    returns the result object.  ``platform=None`` runs wherever JAX runs
    (the CPU rehearsal); ``spec_factory`` replaces the program's farm
    (the control, and the tests' broken paths)."""
    from bench import drive, judge, readout, reference
    from bench.cell import load_cell

    cell = load_cell(root, args.workload)
    config = cell.config
    rec = drive.record(cell, seconds=args.seconds,
                       trace=bool(args.trace), t_start=t_start,
                       platform=platform, device_kinds=device_kinds,
                       spec_factory=spec_factory, log=log)

    # The pool has closed and its nodes have exited: the chips are free.
    import jax

    if platform is not None and jax.devices()[0].platform != platform:
        raise drive.NoAccelerator(
            f"the reference would run on {jax.devices()[0].platform!r}")
    t0 = time.monotonic()
    verdict = judge.judge(rec.jobs, config, lambda lines: reference.farm_answer(
        lines=lines, width=config["width"], max_iters=config["max_iters"],
        escape_radius=config["escape_radius"], plane=config["plane"],
        lines_per_band=config["lines_per_band"]))
    log(f"reference: {verdict['expected']} in "
        f"{time.monotonic() - t0:.3f} s")

    summary = None
    if args.trace:
        from bench import trace_reduce

        try:
            summary = trace_reduce.load(rec.trace_dir)
        finally:
            import shutil

            shutil.rmtree(rec.trace_dir, ignore_errors=True)
    view = readout.RunView(cell=cell, rec=rec, trace=summary)
    metrics = {}
    for m in cell.metrics(bool(args.trace)):
        value = m.reader.read(view)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    device = {"platform": rec.device["platform"], "kind": rec.device["kind"],
              "count": rec.device["count"],
              "memory_peak_bytes": rec.memory_peak_bytes or 0}
    result = {"correct": verdict["correct"], "attempted": len(rec.jobs),
              "failed": judge.failed_jobs(rec.jobs, config),
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops(),
                               "idle_gaps": summary.idle_gaps()}
    result["checks"] = verdict["checks"]
    readout.report(view, result, log)
    return result


def cache_dir(root: pathlib.Path) -> pathlib.Path:
    """The persistent compile cache: one fixed directory in the checkout,
    for this process and, through the launcher's environment, the node."""
    return root / "bench" / ".cache" / "xla"


def main(argv=None, *, root: pathlib.Path = ROOT,
         platform: str | None = "tpu") -> int:
    args = parse_args(argv)
    from bench.cell import UnknownWorkload

    cache_dir(root).mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir(root))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    kinds = json.loads((BENCH / "devices.json").read_text())
    try:
        result = run(root, args, t_start=T_START, platform=platform,
                     device_kinds=None if platform is None else kinds)
    except UnknownWorkload as exc:
        log(f"run_cell: {exc}")
        return 2
    except Exception as exc:  # every failed phase ends here: no result
        log(f"run_cell failed: {type(exc).__name__}: {exc}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
