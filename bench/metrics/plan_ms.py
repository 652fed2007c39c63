"""plan_ms (plan): mean time of the engine's plan stage per batch, from
its stage histogram in metrics_text(), differenced over the window.  The
plan pulls its unique-probe counts to the host, so it ends after its
device work."""

import re

_RX = re.compile(r'^repro_stage_latency_seconds_(sum|count)\{stage="plan"\} '
                 r'(\S+)$', re.M)


def _plan(text):
    got = dict(_RX.findall(text))
    return float(got.get("sum", 0.0)), float(got.get("count", 0.0))


def read(run):
    (s0, n0), (s1, n1) = (_plan(run.window.snap[k]["metrics_text"])
                          for k in ("start", "end"))
    if n1 <= n0:
        return None
    return 1000.0 * (s1 - s0) / (n1 - n0)
