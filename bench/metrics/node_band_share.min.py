"""node_band_share.min (ratio): how evenly the host dispatched the bands
of the jobs that finished inside the window over the configuration's
nodes (the host's per-node counts of each job): the smallest node's bands
times the number of nodes, over all bands.  1.0 is an even spread; a node
that did no band reads 0."""


def read(run):
    rec = run.rec
    by_node: dict[str, int] = {}
    for job in rec.jobs:
        if job.done_at is None or job.done_at > rec.window_end \
                or job.items_by_node is None:
            continue
        for nid, n in job.items_by_node.items():
            by_node[nid] = by_node.get(nid, 0) + n
    total = sum(by_node.values())
    if total <= 0:
        return None
    nodes = max(run.cell.config["nodes"], len(by_node))
    least = min(by_node.values()) if len(by_node) >= nodes else 0
    return least * nodes / total
