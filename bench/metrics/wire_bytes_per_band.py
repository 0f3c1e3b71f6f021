"""wire_bytes_per_band (B): bytes both ways on the nodes' connections
(the program's WireCounters, summed over the nodes) over the traced slice,
per band collected in it."""


def read(run):
    wire = run.rec.wire
    if not wire or wire["bands"] <= 0:
        return None
    return wire["bytes"] / wire["bands"]
