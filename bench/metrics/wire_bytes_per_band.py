"""wire_bytes_per_band (B): bytes both ways on the node's connection
(the program's WireCounters) over the traced slice, per band collected
in it."""


def read(run):
    wire = run.rec.wire
    if not wire or wire["bands"] <= 0:
        return None
    return wire["bytes"] / wire["bands"]
