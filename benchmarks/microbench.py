"""Wall-clock microbenchmarks of each kernel's jnp reference at a reduced
shape, on the default JAX backend (XLA:CPU on a host without a TPU).

These time the reference, never a Pallas kernel: they are host numbers,
not device measurements.  Kernels are measured on the chip through
``KernelProblem.measured`` (see ``chip_smoke.py``)."""

from __future__ import annotations

import time

import jax
import numpy as np

from .common import BENCHMARKS, emit, write_csv

REPEATS = 5


def _time(fn) -> float:
    fn()                                   # compile + warm
    best = np.inf
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def run() -> dict:
    rows = []
    out = {}
    for name, (factory, _) in BENCHMARKS.items():
        prob = factory()
        inputs = prob.make_inputs(jax.random.key(0), small=True)
        cfg = prob.space.sample_distinct(1, seed=0)[0]

        ref_fn = jax.jit(lambda: prob.run_reference(cfg, inputs))
        t_ref = _time(lambda: ref_fn())
        out[name] = {"ref_s": t_ref}
        rows.append([name, "xla_cpu_reference", f"{t_ref * 1e6:.1f}"])
        emit(f"micro/{name}", t_ref * 1e6, "path=xla_cpu_reference")
    write_csv("microbench.csv", ["benchmark", "path", "us_per_call"], rows)
    return out


if __name__ == "__main__":
    run()
