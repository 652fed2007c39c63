"""batch_ms (engine): mean host time per served batch, from
SearchServer.stats (host clock around the search function, up to the
answers on the host) differenced over the window."""


def read(run):
    a, b = (run.window.snap[k]["server"] for k in ("start", "end"))
    batches = b["batches"] - a["batches"]
    if batches <= 0:
        return None
    return 1000.0 * (b["total_latency_s"] - a["total_latency_s"]) / batches
