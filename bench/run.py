"""Runs one benchmark cell and prints its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration with its deployment, its traffic mix and its
metrics are found by name from ``BENCHMARK.json`` (see ``benchlib/spec.py``).
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the result carries its per-layer
metrics.  The run fails, and prints no result, without a TPU or with fewer
chips than the cell asks for.  ``--cpu-rehearsal`` runs the same flow on the
CPU at the configuration's rehearsal size; the benchmark's command never
passes it.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

T_START = time.monotonic()
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at the rehearsal size")
    return ap.parse_args(argv)


def use_compile_cache():
    """JAX's persistent cache, at ``JAX_COMPILATION_CACHE_DIR`` where set,
    else at the fixed ``.jax_cache/`` of the checkout; every program is
    kept, however quickly it compiled, so a warm run compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, rehearsal: bool):
    """The devices JAX found, or None where the run may not go on."""
    import jax

    devs = jax.devices()
    d = devs[0]
    info = dict(platform=d.platform, kind=d.device_kind, count=len(devs))
    if rehearsal:
        return info
    if d.platform != "tpu":
        print(f"run: no TPU: JAX found platform {d.platform!r}",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"run: the cell asks for {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return None
    return info


def main(argv=None, *, fault=None, t_start: float = T_START) -> int:
    args = parse_args(argv)
    from benchlib.spec import Bench

    bench = Bench()
    cell = bench.workload(args.workload)
    device = device_info(cell["chips"], args.cpu_rehearsal)
    if device is None:
        return 2
    if not args.cpu_rehearsal:
        use_compile_cache()
    result = run(bench, cell, args, device, fault=fault, t_start=t_start)
    checks = result["checks"]
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def run(bench, cell, args, device, *, fault=None, t_start=T_START) -> dict:
    from benchlib import cell as C, trace as trace_lib

    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    params = bench.cell(cell["name"])
    if args.cpu_rehearsal:
        cfg = {**cfg, **cfg["rehearsal"]}
        params = {**params, **params["rehearsal"]}
    r, verdict = C.serve(cfg, bench.deployment(cfg), mix, params, args.seed,
                         args.seconds, device, trace=bool(args.trace),
                         fault=fault, t_start=t_start)
    win = r.window
    metrics = {}
    for m in bench.metrics(cell["name"], bool(args.trace)):
        value = bench.reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = dict(correct=verdict["correct"],
               attempted=int(win.in_window.sum()),
               failed=verdict["checks"]["unanswered"]["value"],
               metrics=metrics, device=device)
    if args.trace:
        device["busy_s"] = trace_lib.busy_s(win.trace)
        device["window_s"] = win.trace.window_s
        out["breakdown"] = trace_lib.breakdown(win.trace, pending_ns(win))
    out["checks"] = verdict["checks"]
    return out


def pending_ns(win):
    """Requests waiting at a time on the trace's clock."""
    pending = win.pending()
    t_ns0 = win.trace.window[0]
    return lambda t_ns: pending(win.t0 + (t_ns - t_ns0) / 1e9)


if __name__ == "__main__":
    sys.exit(main())
