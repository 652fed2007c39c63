import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable (e)).

For every assigned (architecture × input-shape) cell and the paper's own
search step, on BOTH production meshes (single-pod 16×16 and multi-pod
2×16×16):

    with mesh:
        lowered  = jax.jit(step, ...).lower(*abstract_args)
        compiled = lowered.compile()
        memory_analysis() / cost_analysis() / collective parse

Two variants per cell (see launch/specs.py): ``exec`` (scanned — the memory
proof) and ``cost`` (unrolled — exact FLOPs/bytes/collective counts).
Results are cached as JSON per (cell × mesh × variant) under
``results/dryrun/`` so reruns only compile what changed.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                # everything
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b
    PYTHONPATH=src python -m repro.launch.dryrun --arch paper-ivf --list
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402


RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")
RESULTS_DIR = os.path.abspath(RESULTS_DIR)

# matches e.g. `%ag.5 = f32[16,1024,100]{2,1,0} all-gather(%x), ...`
COLLECTIVE_RE = re.compile(
    r"=\s*(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)"
    r"\[([\d,]*)\][^\s]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1}


def _group_size(line: str) -> int:
    m = GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = GROUPS_IOTA_RE.search(line)
    if m:  # replica_groups=[n_groups,group_size]<=[N]
        return int(m.group(2))
    return 1


def parse_collectives(hlo_text: str, loop_trip_counts=None):
    """Sums PER-DEVICE link bytes of collective ops in post-SPMD HLO.

    Per-op traffic model (ring algorithms, within ~2× of exact):
      all-gather / all-to-all / collective-permute → result bytes,
      all-reduce → 2 × result bytes,
      reduce-scatter → result bytes × group size (the pre-scatter input).

    Ops inside while bodies appear once in the text; the cost variant is
    fully unrolled so its sums are exact.  For the exec variant we also
    report a loop-corrected estimate using the known scan trip counts.
    """
    per_kind = {}
    total = 0
    in_loop_total = 0
    current_comp_is_loop = False
    for line in hlo_text.splitlines():
        if line.startswith("%") or line.startswith("ENTRY"):
            name = line.split(" ", 1)[0]
            current_comp_is_loop = ("while" in name or "body" in name
                                    or "cond" in name)
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        nbytes = size * DTYPE_BYTES[dt]
        if kind == "all-reduce":
            nbytes *= 2
        elif kind == "reduce-scatter":
            nbytes *= _group_size(line)
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
        total += nbytes
        if current_comp_is_loop:
            in_loop_total += nbytes
    max_trip = max(loop_trip_counts.values()) if loop_trip_counts else 1
    corrected = total + in_loop_total * max(0, max_trip - 1)
    return dict(per_kind=per_kind, total_bytes=total,
                in_loop_bytes=in_loop_total,
                loop_corrected_bytes=corrected)


def _compile_cell(cell, mesh, trip_counts):
    from repro.launch.mesh import n_chips

    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    coll = parse_collectives(hlo, trip_counts)
    return dict(
        chips=n_chips(mesh),
        lower_s=round(t_lower - t0, 2),
        compile_s=round(t_compile - t_lower, 2),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
        ),
        flops=cost.get("flops", 0.0),
        bytes_accessed=cost.get("bytes accessed", 0.0),
        collectives=coll,
    )


def run_cell(arch: str, shape: str, multi_pod: bool, variant: str,
             force: bool = False):
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import LM_ARCHS, build_cell, lm_probe_plan

    mesh_tag = "multipod512" if multi_pod else "pod256"
    out_path = os.path.join(
        RESULTS_DIR, f"{arch}__{shape}__{mesh_tag}__{variant}.json"
    )
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    t0 = time.time()
    record = dict(arch=arch, shape=shape, mesh=mesh_tag, variant=variant)
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        if variant == "cost" and arch in LM_ARCHS:
            # Fully unrolling a 61-layer 512-way module is a multi-hour
            # compile; reported cost is LINEAR in layer counts (while bodies
            # once + per-layer elementwise param ops), so a few small
            # unrolled probes solve for exact full-depth totals.
            probes, solve = lm_probe_plan(arch, shape)
            results = []
            for p in probes:
                cell = build_cell(arch, shape, mesh, "cost", layers=p)
                results.append(
                    _compile_cell(cell, mesh, cell.meta["loop_trip_counts"])
                )
            full = build_cell(arch, shape, mesh, "exec")  # meta only
            pick = lambda key, sub=None: [
                (r[key][sub] if sub else r[key]) for r in results
            ]
            flops = solve(*pick("flops"))
            nbytes = solve(*pick("bytes_accessed"))
            coll_total = solve(
                *[r["collectives"]["total_bytes"] for r in results]
            )
            record.update(
                ok=True,
                chips=results[0]["chips"],
                compile_s=sum(r["compile_s"] for r in results),
                memory=results[-1]["memory"],  # probe memory; exec is truth
                flops=float(flops),
                bytes_accessed=float(nbytes),
                collectives=dict(
                    per_kind={}, total_bytes=float(max(coll_total, 0.0)),
                    in_loop_bytes=0,
                    loop_corrected_bytes=float(max(coll_total, 0.0)),
                ),
                synthesized_from_probes=[list(p) for p in probes],
                probe_results=[
                    dict(flops=r["flops"], bytes=r["bytes_accessed"],
                         coll=r["collectives"]["total_bytes"])
                    for r in results
                ],
                meta={k: v for k, v in full.meta.items()
                      if isinstance(v, (int, float, str, dict, list, tuple))},
            )
        else:
            cell = build_cell(arch, shape, mesh, variant)
            res = _compile_cell(cell, mesh, cell.meta.get("loop_trip_counts"))
            record.update(
                ok=True,
                meta={k: v for k, v in cell.meta.items()
                      if isinstance(v, (int, float, str, dict, list, tuple))},
                **res,
            )
    except Exception as e:  # a failing cell is a bug — record it loudly
        record.update(ok=False, error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    status = "OK " if record.get("ok") else "FAIL"
    print(f"[{status}] {arch} × {shape} × {mesh_tag} × {variant} "
          f"({time.time()-t0:.0f}s)", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod256", "multipod512", "both"],
                    default="both")
    ap.add_argument("--variant", choices=["exec", "cost", "both"],
                    default="both")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.launch.specs import list_cells

    cells = list_cells()
    if args.list:
        for a, s, skip in cells:
            print(f"{a:20s} {s:15s} {'SKIP: ' + skip if skip else ''}")
        return

    meshes = {"pod256": [False], "multipod512": [True],
              "both": [False, True]}[args.mesh]
    variants = {"exec": ["exec"], "cost": ["cost"],
                "both": ["exec", "cost"]}[args.variant]

    n_ok = n_fail = n_skip = 0
    for arch, shape, skip in cells:
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape != args.shape:
            continue
        if skip:
            n_skip += 1
            print(f"[SKIP] {arch} × {shape}: {skip}")
            continue
        for mp in meshes:
            for v in variants:
                rec = run_cell(arch, shape, mp, v, force=args.force)
                if rec.get("ok"):
                    n_ok += 1
                else:
                    n_fail += 1
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed, "
          f"{n_skip} cells skipped (documented)")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
