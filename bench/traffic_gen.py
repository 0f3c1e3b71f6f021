"""The one reader of the benchmark's traffic, driven by a mix's data file.

A mix (``bench/traffic/<mix>.json``) says how jobs reach the pool.  The
one loop there is, ``"loop": "closed"``, has one client submit a job,
wait for its last result, and submit the next, for the whole window.
``"job_lines"`` is the size of one job in lines of the configuration's
plane; ``"all"`` means the configuration's whole plane.
"""

from __future__ import annotations

LOOPS = ("closed",)


def job_lines(traffic: dict, config: dict) -> int:
    lines = traffic["job_lines"]
    return config["lines"] if lines == "all" else int(lines)


def validate(traffic: dict) -> None:
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}, "
                         f"got {traffic.get('loop')!r}")
    if "job_lines" not in traffic:
        raise ValueError("traffic has no job_lines")
