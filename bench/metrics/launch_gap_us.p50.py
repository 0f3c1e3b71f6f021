"""launch_gap_us.p50 (us): median device-idle time between one kernel
event and the next in the traced slice: the per-band host cost as the
chip sees it."""

import statistics

from bench.readout import KERNEL_EVENT


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace.launch_gaps_ns(KERNEL_EVENT)
    return statistics.median(gaps) / 1e3 if gaps else None
