"""Public N-body op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import nbody as nbody_pallas

# tuned on the analytical v5e model; refreshed by benchmarks.tune_kernels.
DEFAULT_CONFIG = {
    "block_i": 128, "block_j": 2048, "layout": "soa", "unroll_j": 1,
    "rsqrt_method": "approx", "compute_dtype": "f32",
}


def nbody(pos, mass, config: dict | None = None,
          interpret: bool = False):
    """``pos``: (3, N); ``mass``: (N,) -> (3, N) accelerations."""
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return nbody_pallas(pos, mass, interpret=interpret, **cfg)
