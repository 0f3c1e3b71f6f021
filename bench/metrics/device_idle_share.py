"""device_idle_share (%): the share of the traced slice in which no
operation ran on the chip (each node's device trace, over its own window;
averaged over the nodes)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
