"""The benchmark's command rehearsed on the CPU: a cell added by files and
a ``workloads`` entry alone runs end to end through a real one-node
``ClusterService`` (kernel in the Pallas interpreter) and prints a
well-formed last line; so does a cell of two nodes, each of its own
device, with bands from both; a control job reaches every node of a real
pool once; an unknown workload, and a checkout without the program, exit
non-zero and print no result."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

from bench import drive, probes, run_cell
from bench.tests import rig

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def test_a_cell_added_by_files_alone_runs_end_to_end_on_cpu(
        tmp_path, monkeypatch, capsys):
    # A new mix (data only) and a cell that names it: no code changes.
    mix = {"loop": "closed", "job_lines": 16}
    root = rig.make_root(tmp_path, [rig.cell("tiny.quarter", "quarter")],
                         traffic={"quarter": mix})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")  # main sets it
    rc = run_cell.main(["--workload", "tiny.quarter", "--seed",
                        str(2**31 + 11), "--seconds", "1.5", "--trace", "0"],
                       root=root, platform=None)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = rig.last_json_line(out.out)
    assert list(result) == RESULT_KEYS  # checks come last
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"mpoints_per_s", "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    device = result["device"]
    assert (device["platform"], device["count"]) == ("cpu", 1)
    assert "memory_peak_bytes" in device
    assert all(c == {"value": 0, "limit": 0}
               for c in result["checks"].values())
    # Every number compared closes standard error too.
    tail = out.err.strip().splitlines()[-len(result["checks"]):]
    assert [ln.split("=")[0] for ln in tail] == [
        f"check {k}" for k in result["checks"]]
    assert (root / "bench" / ".cache" / "xla").is_dir()


def test_a_cell_of_two_nodes_runs_end_to_end_on_cpu(tmp_path, monkeypatch,
                                                   capsys):
    root = rig.make_root(tmp_path, [rig.cell("tiny.two", "batch", chips=2)],
                         config={"nodes": 2})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    recordings = []
    record = drive.record

    def keep(*args, **kwargs):
        recordings.append(record(*args, **kwargs))
        return recordings[-1]

    monkeypatch.setattr(drive, "record", keep)
    rc = run_cell.main(["--workload", "tiny.two", "--seed", str(2**31 + 5),
                        "--seconds", "1.5", "--trace", "0"],
                       root=root, platform=None)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = rig.last_json_line(out.out)
    assert result["correct"] is True and result["failed"] == 0
    assert (result["device"]["platform"], result["device"]["count"]) == (
        "cpu", 2)
    [rec] = recordings
    assert len(set(rec.device["pids"])) == 2
    bands: dict[str, int] = {}
    for job in rec.jobs:
        for nid, n in (job.items_by_node or {}).items():
            bands[nid] = bands.get(nid, 0) + n
    assert set(bands) == {"node0", "node1"} and min(bands.values()) > 0
    assert set(rec.gc_pauses) == {"host", "node0", "node1"}


def test_a_control_job_reaches_every_node_once(tmp_path, monkeypatch):
    from repro.cluster.service import ClusterService

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with ClusterService(nodes=2, workers=2) as svc:
        nodes = svc.pool_alive()
        assert nodes == ["node0", "node1"]
        # A per-node argument reaches its own node: ``str`` echoes it.
        assert drive.every_node(svc, str, {"node0": "a", "node1": "b"}) == {
            "node0": "a", "node1": "b"}
        reports = drive.every_node(svc, probes.device_report,
                                   dict.fromkeys(nodes, 0))
        assert sorted(reports) == nodes
        assert len({r["pid"] for r in reports.values()}) == 2
        # Two items a node, one draw a node: every item of a node answers
        # what its first one drew.
        spec = probes.every_node_spec(os.urandom, dict.fromkeys(nodes, 16),
                                      2, str(tmp_path))
        answers = svc.submit(spec).result()
        drawn = dict(answers)
        assert len(answers) == 4 and sorted(drawn) == nodes
        assert all(drawn[nid] == answer for nid, answer in answers)
        assert len(set(drawn.values())) == 2
        assert sorted(os.listdir(tmp_path)) == nodes


def test_an_unknown_workload_is_refused(tmp_path, monkeypatch, capsys):
    root = rig.make_root(tmp_path, [rig.cell("tiny.batch", "batch")])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    rc = run_cell.main(["--workload", "no.such_cell", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       root=root, platform=None)
    out = capsys.readouterr()
    assert rc == 2
    assert rig.last_json_line(out.out) is None
    assert "unknown workload 'no.such_cell'" in out.err


def test_a_checkout_without_the_program_exits_nonzero_without_a_result(
        tmp_path):
    # Only BENCHMARK.json and the benchmark's own files: no src/.
    root = tmp_path / "bare"
    shutil.copytree(rig.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(rig.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         "mandelbrot_paper.batch", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert rig.last_json_line(proc.stdout) is None
    assert "run_cell failed" in proc.stderr


def test_the_benchmark_file_names_a_reader_for_every_metric():
    bench = json.loads((rig.REPO / "BENCHMARK.json").read_text())
    from bench.cell import load_cell

    for w in bench["workloads"]:
        cell = load_cell(rig.REPO, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]
        moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
        e2e = {m.name for m in cell.end_to_end}
        assert all(moved[m.name] in e2e for m in cell.per_layer)
    assert pathlib.Path(rig.BENCH / "run_cell.py").is_file()
