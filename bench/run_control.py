"""Run a cell once with the control in the program's place.

    python3 bench/run_control.py --workload <name> --seed <n> \
        --seconds <s>

The run is one run as ``bench/run_cell.py`` makes it, with every job's
bands rendered by the bf16 reference (``bench/control.py``) in place of
the program's kernel; its last line gives ``correct`` and the numbers
compared.  The control has to come out not correct on every seed; the
exit code is 0 when it does.  One seed per process: after its pool
closes the process holds the chip for the reference.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()

import pathlib  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)

    from bench import run_cell
    from bench.control import control_spec

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_cell.cache_dir(ROOT))
    kinds = json.loads((BENCH / "devices.json").read_text())
    args.trace = 0
    result = run_cell.run(ROOT, args, t_start=T_START, device_kinds=kinds,
                          spec_factory=control_spec)
    print(json.dumps({"seed": args.seed, "correct": result["correct"],
                      "attempted": result["attempted"],
                      "checks": result["checks"]}), flush=True)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
