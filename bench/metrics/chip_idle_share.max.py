"""chip_idle_share.max (%): the idlest chip's share of the traced slice in
which no operation ran on it (each node's device trace, over its own
window): a chip the dispatcher starves, which the mean over the chips,
``device_idle_share``, hides."""


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    return 100.0 * max(chip.idle_share for chip in run.trace.chips)
