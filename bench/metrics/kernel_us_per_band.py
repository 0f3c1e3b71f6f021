"""kernel_us_per_band (us): device time of the Mandelbrot kernel's events
in the traced slice, over their number (one event per band)."""

from bench.readout import KERNEL_EVENT


def read(run):
    if run.trace is None:
        return None
    events = run.trace.kernel_events(KERNEL_EVENT)
    if not events:
        return None
    return sum(e.end - e.start for e in events) / len(events) / 1e3
