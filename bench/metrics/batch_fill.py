"""batch_fill (server): requests per served batch over the batch size, in
per cent, from SearchServer.stats differenced over the window."""


def read(run):
    a, b = (run.window.snap[k]["server"] for k in ("start", "end"))
    batches = b["batches"] - a["batches"]
    if batches <= 0:
        return None
    return 100.0 * (b["requests"] - a["requests"]) / batches / run.batch_size
