"""Decide ``correct``: every timed job's answer against the plain reference.

Each job of a cell renders the same plane, so the reference computes each
distinct job size once, after the pool has closed, and every job's
``(iterations, white, points)`` is held against it.  All four numbers
compared are exact, so every limit is 0:

- ``missing_jobs``: jobs of the window that erred or never answered;
- ``band_gap``: the most bands by which a job's points fell short of (or
  exceeded) the bands it was given;
- ``iteration_gap``: the widest gap, over the jobs, between a job's
  summed escape counts and the reference's;
- ``white_gap``: the same for the count of escaped points.
"""

from __future__ import annotations

from typing import Callable

LIMITS = {"missing_jobs": 0, "band_gap": 0, "iteration_gap": 0,
          "white_gap": 0}


def judge(jobs, config: dict,
          answer: Callable[..., tuple[int, int, int]]) -> dict:
    """The numbers compared, each with its limit, and ``correct``.

    ``answer(lines=...)`` is the reference's ``(iterations, white,
    points)`` for a job over that many lines of the configuration."""
    expected: dict[int, tuple[int, int, int]] = {}
    points_per_band = config["lines_per_band"] * config["width"]
    gaps = dict.fromkeys(LIMITS, 0)
    for job in jobs:
        if job.result is None:
            gaps["missing_jobs"] += 1
            continue
        if job.lines not in expected:
            expected[job.lines] = answer(lines=job.lines)
        iters, white, points = expected[job.lines]
        got_iters, got_white, got_points = job.result
        gaps["band_gap"] = max(gaps["band_gap"],
                               -(-abs(points - got_points) // points_per_band))
        gaps["iteration_gap"] = max(gaps["iteration_gap"],
                                    abs(iters - got_iters))
        gaps["white_gap"] = max(gaps["white_gap"], abs(white - got_white))
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in gaps.items()}
    return {"correct": all(v <= LIMITS[k] for k, v in gaps.items()),
            "checks": checks, "expected": expected}


def failed_jobs(jobs, config: dict) -> int:
    """Jobs that erred, timed out or came back short of their bands."""
    points_per_band = config["lines_per_band"] * config["width"]
    return sum(1 for j in jobs
               if j.result is None or j.result[2] != j.bands * points_per_band)
