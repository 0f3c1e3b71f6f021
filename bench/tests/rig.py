"""A checkout of the benchmark at a CPU size, for the tests: a root
holding a ``BENCHMARK.json`` whose cells use the test-only configuration
``data/mandelbrot_tiny.json`` (the farm shrunk, the kernel in the Pallas
interpreter), the benchmark's own traffic mixes and metric readers, and
whatever mixes and configuration changes a test adds."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
TINY = "mandelbrot_tiny"


def make_root(tmp: pathlib.Path, workloads: list[dict],
              traffic: dict[str, dict] | None = None,
              config: dict | None = None) -> pathlib.Path:
    """A root under ``tmp`` with the benchmark's metrics and mixes, the
    tiny configuration with the keys of ``config`` changed, the extra mixes
    ``traffic``, and ``workloads`` as its only cells.  A metric that lists
    cells lists every one of them."""
    root = tmp / "checkout"
    (root / "bench" / "configs").mkdir(parents=True)
    for d in ("metrics", "traffic"):
        shutil.copytree(BENCH / d, root / "bench" / d)
    for name, mix in (traffic or {}).items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    tiny = json.loads((BENCH / "tests" / "data" / f"{TINY}.json").read_text())
    (root / "bench" / "configs" / f"{TINY}.json").write_text(
        json.dumps({**tiny, **(config or {})}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w["name"] for w in workloads]
    bench["configs"] = [{"name": TINY, "source": "test only",
                         "file": f"bench/configs/{TINY}.json",
                         "reduced": [], "why": "CPU rehearsal"}]
    bench["workloads"] = workloads
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def cell(name: str, traffic: str, chips: int = 1) -> dict:
    return {"name": name, "config": TINY, "traffic": traffic,
            "chips": chips, "why": "CPU rehearsal"}


def last_json_line(text: str) -> dict | None:
    lines = [ln for ln in text.splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None
