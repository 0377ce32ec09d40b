import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any jax import (device count locks at
first init).  512 placeholder host devices back the production meshes:
16×16 single-pod and 2×16×16 multi-pod.

Per cell this driver:
  1. builds the model + sharding plan (launch.steps.plan_cell),
  2. ``jit(step).lower(**input_specs)`` — ShapeDtypeStructs, no allocation,
  3. ``.compile()`` — proves the sharding config is coherent (no mismatched
     collectives, no unpartitionable ops) and yields cost/memory analyses,
  4. extracts the three roofline terms (repro.roofline) and writes one JSON
     per cell under ``experiments/dryrun/``.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all            # every assigned cell
    python -m repro.launch.dryrun --all --jobs 8   # subprocess per cell
"""

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             overrides: dict | None = None, probe: bool = False,
             optimized: bool = False, aspect: str | None = None) -> dict:
    import jax
    from jax.sharding import AxisType

    from ..configs import ARCHS, SHAPES
    from ..roofline import analyze_compiled, model_flops, roofline_report
    from .mesh import make_production_mesh
    from .steps import lower_cell, optimize_config, plan_cell

    cfg = ARCHS[arch]
    if aspect:          # §Perf: DPxTP aspect is itself a sharding tunable
        d, m = (int(x) for x in aspect.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        mesh_name = f"{d}x{m}"
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "2x16x16" if multi_pod else "16x16"
    if optimized:
        cfg = optimize_config(cfg, mesh)
        mesh_name += ".opt"
    chips = mesh.devices.size

    t0 = time.perf_counter()
    plan = plan_cell(cfg, shape, mesh, **(overrides or {}))
    lowered = lower_cell(plan, mesh)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mf = model_flops(cfg, SHAPES[shape], microbatches=plan.microbatches)
    report = analyze_compiled(
        compiled, chips=chips, arch=arch, shape=shape, mesh=mesh_name,
        model_flops_value=mf)
    mem = compiled.memory_analysis()
    out = {
        **report.to_dict(),
        "microbatches": plan.microbatches,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "devices": chips,
        "jax_version": jax.__version__,
    }

    if probe:                     # loop-corrected roofline terms (§Roofline)
        from ..roofline.probe import corrected_report
        t0 = time.perf_counter()
        corr, res = corrected_report(cfg, shape, mesh, arch=arch,
                                     mesh_name=mesh_name,
                                     model_flops_value=mf)
        corr.peak_memory_per_chip = report.peak_memory_per_chip
        out["corrected"] = corr.to_dict()
        out["probe_breakdown"] = {
            k: {"flops": v.flops, "hbm": v.hbm, "coll": v.coll}
            for k, v in res["breakdown"].items()}
        out["probe_s"] = time.perf_counter() - t0
        print(roofline_report(corr))
    else:
        print(roofline_report(report))

    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}.{shape}.{mesh_name}.json"
    path.write_text(json.dumps(out, indent=1))
    print(f"  lower {t_lower:.1f}s  compile {t_compile:.1f}s  -> {path}")
    return out


def all_cells() -> list[tuple[str, str]]:
    from ..configs import ARCHS, cells_for
    return [(a, s) for a in ARCHS for s in cells_for(a)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch × shape) cell")
    ap.add_argument("--both-meshes", action="store_true",
                    help="with --all: run single-pod AND multi-pod")
    ap.add_argument("--jobs", type=int, default=1,
                    help="with --all: concurrent subprocesses")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="add loop-corrected roofline terms (single-pod)")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper SPMD optimizations (writes *.opt.json)")
    ap.add_argument("--aspect", default=None,
                    help="override single-pod mesh aspect, e.g. 64x4")
    args = ap.parse_args()
    out_dir = Path(args.out)

    if not args.all:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape (or --all) required")
        run_cell(args.arch, args.shape, args.multi_pod, out_dir,
                 probe=args.probe, optimized=args.opt, aspect=args.aspect)
        return

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = [(a, s, mp) for a, s in all_cells() for mp in meshes]
    if args.skip_existing:
        cells = [(a, s, mp) for a, s, mp in cells
                 if not (out_dir / f"{a}.{s}.{'2x16x16' if mp else '16x16'}"
                         ".json").exists()]
    print(f"{len(cells)} cells to run", flush=True)
    if args.jobs <= 1:
        failures = []
        for a, s, mp in cells:
            try:
                run_cell(a, s, mp, out_dir, probe=(args.probe and not mp))
            except Exception as e:           # noqa: BLE001 — report & continue
                failures.append((a, s, mp, repr(e)))
                print(f"FAIL {a} {s} multi_pod={mp}: {e!r}", flush=True)
        if failures:
            sys.exit(f"{len(failures)} cells failed: {failures}")
        return

    # subprocess per cell: isolates compile memory, enables parallelism
    procs: list[tuple[subprocess.Popen, tuple]] = []
    pending = list(cells)
    failures = []
    while pending or procs:
        while pending and len(procs) < args.jobs:
            a, s, mp = pending.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--out", str(out_dir)]
            if mp:
                cmd.append("--multi-pod")
            elif args.probe:
                cmd.append("--probe")
            procs.append((subprocess.Popen(cmd), (a, s, mp)))
        still = []
        for p, cell in procs:
            if p.poll() is None:
                still.append((p, cell))
            elif p.returncode != 0:
                failures.append(cell)
                print(f"FAIL {cell}", flush=True)
        procs = still
        time.sleep(0.5)
    if failures:
        sys.exit(f"{len(failures)} cells failed: {failures}")


if __name__ == "__main__":
    main()
