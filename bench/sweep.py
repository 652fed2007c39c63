"""Knee sweep: builds a cell's system once and offers it a ladder of rates.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 100,200,400

Each rate gets its own open-loop window of the cell's traffic mix, read by
the cell's own metric readers (``bench/metrics/``).  One line per rate:
the offered rate, ``qps``, ``p50_ms``, ``p95_ms``, ``batch_fill``,
``batch_ms``, and the backlog (requests sent but not yet answered) at the
middle and at the end of the window.  The knee is the highest rate whose
backlog does not grow.
"""

from __future__ import annotations

import argparse
import json
import sys

from run import device_info, use_compile_cache

READ = ("qps", "p50_ms", "p95_ms", "batch_fill", "batch_ms")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from benchlib import cell as C
    from benchlib.spec import Bench

    bench = Bench()
    cell = bench.workload(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    if args.cpu_rehearsal:
        cfg = {**cfg, **cfg["rehearsal"]}
    device = device_info(cell["chips"], args.cpu_rehearsal)
    if device is None:
        return 2
    if not args.cpu_rehearsal:
        use_compile_cache()
    readers = {name: bench.reader(name) for name in READ}
    watch = C.Watch()
    system = None
    try:
        system = C.start(cfg, mix, args.seed,
                         on_tpu=device["platform"] == "tpu")
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            win = C.open_loop(system, mix, rate, args.seconds, args.seed + i,
                              watch)
            r = C.view(win, batch_size=cfg["batch"],
                       device_kind=device["kind"],
                       platform=device["platform"])

            def backlog(t):
                return int(np.sum((win.sent <= t) & ~(win.done <= t)))

            print(json.dumps(dict(
                rate=rate, **{name: read(r) for name, read in readers.items()},
                backlog_mid=backlog(win.t0 + win.seconds / 2),
                backlog_end=backlog(win.t0 + win.seconds),
                compiles_in_window=win.compiles["programs"],
                host=win.host())), flush=True)
    finally:
        if system is not None:
            system.free()
        watch.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
