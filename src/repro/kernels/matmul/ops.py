"""Public GEMM op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import gemm as gemm_pallas

# tuned on the analytical v5e model (see benchmarks/data); refreshed by
# `python -m benchmarks.tune_kernels`.
DEFAULT_CONFIG = {
    "block_m": 512, "block_n": 256, "block_k": 512, "unroll_k": 1,
    "grid_order": "mn", "split_k": 1, "acc_dtype": "f32", "rhs_layout": "kn",
}


def gemm(a, b, c, alpha=1.0, beta=1.0, config: dict | None = None,
         interpret: bool = False):
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    b_in = b if cfg["rhs_layout"] == "kn" else b.T
    return gemm_pallas(a, b_in, c, alpha=alpha, beta=beta,
                       interpret=interpret, **cfg)
