"""Readings that set a cell's limits: the program and its control.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5,6

For each of ``--seeds`` it runs the cell as a benchmark run does (set-up,
one window of the cell's traffic at the cell's rate, the check) and prints
the numbers the check compares (``score_gap``, ``id_mismatch``,
``answer_faults``, ``unanswered``).  For each of ``--control-seeds`` it
does the same with the control, the build of the program that the
deployment names (``CONTROL``; the ``topics`` deployment's is the
program's own int8 (SQ8) lists, one precision below the configuration's
bf16), compared with the same reference.  All in one process, so the
programs compile once.  The benchmark's runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys

from run import device_info, use_compile_cache


def readings(bench, workload: str, seed: int, seconds: float, variant: str,
             device: dict, rehearsal: bool, fault=None) -> dict:
    from benchlib import cell as C

    cell = bench.workload(workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    params = bench.cell(workload)
    if rehearsal:
        cfg = {**cfg, **cfg["rehearsal"]}
        params = {**params, **params["rehearsal"]}
    _, verdict = C.serve(cfg, bench.deployment(cfg), mix, params, seed,
                         seconds, dict(device), variant=variant, fault=fault)
    return dict(seed=seed, variant=variant or "program",
                correct=verdict["correct"],
                values={k: v["value"] for k, v in verdict["checks"].items()},
                info=verdict["info"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    from benchlib.spec import Bench

    bench = Bench()
    cell = bench.workload(args.workload)
    device = device_info(cell["chips"], args.cpu_rehearsal)
    if device is None:
        return 2
    if not args.cpu_rehearsal:
        use_compile_cache()
    runs = [(int(s), "") for s in args.seeds.split(",") if s]
    ctl = bench.deployment(bench.config(cell["config"])).CONTROL
    runs += [(int(s), ctl) for s in args.control_seeds.split(",") if s]
    for seed, variant in runs:
        print(json.dumps(readings(bench, args.workload, seed, args.seconds,
                                  variant, device, args.cpu_rehearsal)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
