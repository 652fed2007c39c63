"""scan_slots (plan): mean slot-table size per scanned tile, from the
engine's u_cap histogram differenced over the window: the scan work that
probe deduplication leaves."""


def read(run):
    a, b = (run.window.snap[k]["u_cap_hist"] for k in ("start", "end"))
    n = {u: b[u] - a.get(u, 0) for u in b}
    tiles = sum(n.values())
    if tiles <= 0:
        return None
    return sum(u * c for u, c in n.items()) / tiles
