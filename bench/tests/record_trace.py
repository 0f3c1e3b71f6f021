"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb.gz>

On a machine with a TPU: one warm pool of the paper configuration (one
node-loader, 4 workers, the compiled kernel) runs a farm of BANDS bands
of 4 x 5600 points at escape 1000, with the node's profiler on around
it, exactly as a traced run of the benchmark starts and stops it.  The
trace is written gzipped; the count of bands and of the kernel's events
is printed for the test to check against.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import pathlib
import shutil
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
BANDS = 12

if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def main(out: str) -> int:
    from bench import drive, probes, run_cell
    from bench.cell import load_cell
    from repro.cluster.service import ClusterService

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_cell.cache_dir(ROOT))
    cell = load_cell(ROOT, "mandelbrot_paper.batch")
    config = cell.config
    trace_dir = tempfile.mkdtemp(prefix="bench-fixture-")
    try:
        with ClusterService(nodes=1, workers=config["workers"],
                            preload=drive.PRELOAD) as svc:
            drive.boot(svc, cell, drive.job_spec(config, 16), platform="tpu",
                       device_kinds=None, log=print)
            svc.submit(probes.one_item_spec(probes.start_trace, trace_dir),
                       timeout=60).result()
            result = svc.submit(drive.job_spec(config, 4 * BANDS),
                                timeout=60).result()
            svc.submit(probes.one_item_spec(probes.stop_trace, 0),
                       timeout=120).result()
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        with open(path, "rb") as src, gzip.open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"bands": BANDS, "result": list(result),
                      "bytes": os.path.getsize(out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
