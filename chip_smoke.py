#!/usr/bin/env python3
"""Smoke test of the measured tuning path on one TPU chip.

Run it from the root of a checkout, on a machine whose JAX sees a TPU:

    python chip_smoke.py [--seed N]

It runs in this one process and starts no other.  Its phases:

1. device: stops, non-zero, unless JAX's first device is a TPU;
2. kernels: flash attention at qwen3-8b's widths, GEMM 4096^3 in bf16,
   nbody and hotspot at their default shapes, each compiled for the chip
   (never interpreted) with its ``ops.py`` default config, run on seeded
   inputs and compared with its jnp reference;
3. campaign: one measured tuning session through ``run_session`` on flash
   attention at the same shape (random search, journaled under
   ``experiments/chip_smoke``).  Every config is compiled and timed on the
   chip; a config the compiler refuses must come back as an invalid trial
   carrying the compiler's error, and the best config's output must match
   the reference.

Each result goes on its own line.  The last line of a run in which every
phase passed is ``{"ok": true, "device": {...}}``; otherwise the script
says on stderr what failed and exits 1, with no such line.  The times it
prints are smoke output, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STORE = ROOT / "experiments" / "chip_smoke"
#: target bodies whose nbody accelerations are checked against all N: the
#: full reference would hold a (3, N, N) array (~200 GB at N = 131072)
NBODY_ROWS = 1024
#: configs the campaign measures
BUDGET = 16


def log(msg: str) -> None:
    print(msg, flush=True)


def device_phase(jax) -> dict | None:
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX finds no device: {e}", file=sys.stderr)
        return None
    d = devices[0]
    if d.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX's first device is {d.platform} "
              f"({d.device_kind}), and this smoke runs only on a TPU",
              file=sys.stderr)
        return None
    log(f"device: {d.platform} {d.device_kind} count={len(devices)} "
        f"jax={jax.__version__}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def nbody_rows(pos, mass, rows):
    """``nbody_reference``'s accelerations of the bodies ``rows`` alone,
    from all N bodies: (3, len(rows))."""
    import jax.numpy as jnp
    from repro.kernels.nbody import ref
    d = pos[:, None, :] - pos[:, rows, None]            # (3, r, N)
    r2 = (d * d).sum(axis=0) + ref.EPS2
    w = mass[None, :] / (r2 * jnp.sqrt(r2))
    return ref.G * (d * w[None, :, :]).sum(axis=2)


def check_output(name: str, prob, config, got, want) -> bool:
    import numpy as np
    from repro.kernels.common import rel_l2
    err = rel_l2(got, want)
    worst = float(np.max(np.abs(np.asarray(got, np.float64)
                                - np.asarray(want, np.float64))))
    tol = prob.tolerance(config)
    ok = bool(np.isfinite(np.asarray(got, np.float64)).all()) and err <= tol
    log(f"  {name}: rel_l2={err:.3e} (tol {tol:g}) max_abs_err={worst:.3e} "
        f"shape={tuple(np.shape(got))} {'ok' if ok else 'FAIL'}")
    return ok


def kernel_phase(jax, seed: int) -> tuple[list[str], dict]:
    """Compile, run and check each main-path kernel; returns the failures
    and the attention problem's inputs and reference for the campaign."""
    from repro.kernels.attention import ops as attention_ops
    from repro.kernels.attention.space import AttentionProblem
    from repro.kernels.hotspot import ops as hotspot_ops
    from repro.kernels.hotspot.space import HotspotProblem
    from repro.kernels.matmul import ops as matmul_ops
    from repro.kernels.matmul.space import GemmProblem
    from repro.kernels.nbody import ops as nbody_ops
    from repro.kernels.nbody.space import NbodyProblem

    cases = [("flash_attention", AttentionProblem(),
              attention_ops.DEFAULT_CONFIG),
             ("gemm", GemmProblem(), matmul_ops.DEFAULT_CONFIG),
             ("nbody", NbodyProblem(), nbody_ops.DEFAULT_CONFIG),
             ("hotspot", HotspotProblem(), hotspot_ops.DEFAULT_CONFIG)]
    failures: list[str] = []
    keep: dict = {}
    log("kernels:")
    for i, (name, prob, config) in enumerate(cases):
        try:
            key = jax.random.fold_in(jax.random.key(seed), i)
            inputs = prob.make_inputs(key, small=False)
            t0 = time.perf_counter()
            run = prob.compile_kernel(config, inputs)
            compile_s = time.perf_counter() - t0
            got = jax.block_until_ready(run())
            log(f"  {name}: shape={prob.shape} config={config} "
                f"compile_s={compile_s:.2f}")
            with jax.default_matmul_precision("highest"):
                if name == "nbody":
                    rows = jax.random.choice(
                        jax.random.fold_in(key, 1), prob.shape["n"],
                        (NBODY_ROWS,), replace=False)
                    got = got[:, rows]
                    want = nbody_rows(inputs["pos"], inputs["mass"], rows)
                else:
                    want = prob.run_reference(config, inputs)
                want = jax.block_until_ready(want)
            if not check_output(name, prob, config, got, want):
                failures.append(f"kernel {name} does not match its reference")
            if name == "flash_attention":
                keep = {"problem": prob, "inputs": inputs, "want": want}
            del run, got, want
        except Exception as e:
            traceback.print_exc()
            failures.append(f"kernel {name} raised {e!r:.400}")
    return failures, keep


def campaign_phase(problem, inputs, want, *, seed: int) -> list[str]:
    """One measured session on the chip; returns the failures."""
    import math

    from repro.orchestrator import SessionSpec, SessionStore, run_session

    shutil.rmtree(STORE, ignore_errors=True)     # nothing read from before
    store = SessionStore(STORE)
    spec = SessionSpec(problem=problem.name, tuner="random", budget=BUDGET,
                       seed=seed, workers=1)
    log(f"campaign: session {spec.session_id} store={STORE}")
    t0 = time.perf_counter()
    res = run_session(spec, problem=problem.measured(inputs), store=store)
    wall_s = time.perf_counter() - t0

    measured = [t for t in res.trials if t.valid]
    poisoned = [t for t in res.trials if t.info.get("poison")]
    refused = [t for t in res.trials
               if not t.valid and not t.info.get("poison")]
    log(f"  trials={len(res.trials)} measured={len(measured)} "
        f"refused={len(refused)} poisoned={len(poisoned)} "
        f"wall_s={wall_s:.1f}")
    for t in refused + poisoned:
        first = str(t.info.get("error", "")).partition("\n")[0]
        log(f"  invalid {t.config}: {first[:240]}")

    failures = []
    if len(res.trials) != BUDGET:
        failures.append(f"session recorded {len(res.trials)} trials, "
                        f"not {BUDGET}")
    if not measured:
        failures.append("session measured no config")
    if any(not (math.isfinite(t.objective) and t.objective > 0)
           for t in measured):
        failures.append("a measured trial has no finite positive time")
    if any(not t.info.get("error") for t in refused):
        failures.append("a refused config carries no compiler error")
    if poisoned:
        failures.append(f"{len(poisoned)} config(s) failed while measured")
    journal = store.load_journal(spec.session_id, problem.space, spec.arch)
    if len(journal) != len(res.trials) or any(
            not t.valid and not t.info.get("error") for _, t in journal):
        failures.append("the journal lost a trial or a compiler error")
    if measured:
        best = res.best
        log(f"  best {best.config}: {best.objective * 1e3:.3f} ms")
        got = problem.compile_kernel(best.config, inputs)()
        if not check_output("best config", problem, best.config, got, want):
            failures.append("the best config does not match the reference")
    return failures


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    # seed 1's random sample of the attention space holds configs the v5e
    # compiler refuses for VMEM, so the refusal path runs too
    ap.add_argument("--seed", type=int, default=1,
                    help="seeds the inputs and the tuner (default 1)")
    args = ap.parse_args(argv)

    import jax
    device = device_phase(jax)
    if device is None:
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.core import spacetable
        from repro.kernels.common import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{ROOT / 'src'}: {e}", file=sys.stderr)
        return 1
    spacetable.set_cache_dir(None)   # no search-space table from a past run
    cache = Path(use_compile_cache())
    log(f"compile cache: {cache}")

    failures, attention = kernel_phase(jax, args.seed)
    if attention:
        try:
            failures += campaign_phase(
                attention["problem"], attention["inputs"], attention["want"],
                seed=args.seed)
        except Exception as e:
            traceback.print_exc()
            failures.append(f"campaign raised {e!r:.400}")
    else:
        failures.append("campaign skipped: flash attention failed")
    n_cached = sum(p.is_file() for p in cache.rglob("*")) \
        if cache.is_dir() else 0
    log(f"compile cache entries: {n_cached}")

    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
