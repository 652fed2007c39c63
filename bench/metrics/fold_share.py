"""fold_share (scan): the scan kernel's top-k insertion passes over the k
passes per grid step that a fold of fixed length runs, in per cent: the
engine's counters ``repro_engine_fold_passes`` and
``repro_engine_fold_k_passes`` in metrics_text(), differenced over the
window.  A program without them reads None."""

import re


def _counter(text: str, name: str):
    m = re.search(r"^repro_engine_" + name + r" (\S+)$", text, re.M)
    return None if m is None else float(m.group(1))


def read(run):
    delta = {}
    for name in ("fold_passes", "fold_k_passes"):
        a, b = (_counter(run.window.snap[k]["metrics_text"], name)
                for k in ("start", "end"))
        if a is None or b is None:
            return None
        delta[name] = b - a
    if delta["fold_k_passes"] <= 0:
        return None
    return 100.0 * delta["fold_passes"] / delta["fold_k_passes"]
