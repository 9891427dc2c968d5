"""collective_share.tp4: TP collectives: share of the device's busy time in
collective operations (all-reduce, all-gather, ...), per device and averaged
over the cell's chips, over the traced steps. Moves itl_p95_ms."""


def read(run):
    t = run.trace
    if t is None or not t.busy_s:
        return None
    return 100.0 * t.collective_s / t.busy_s
