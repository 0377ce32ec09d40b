"""Public ExpDist op (localization-microscopy registration distance)."""

from __future__ import annotations

from .kernel import expdist as expdist_pallas

DEFAULT_CONFIG = {
    "block_i": 256, "block_j": 1024, "use_column": 0, "n_y_blocks": 1,
    "unroll_j": 1, "exp_variant": "exp", "compute_dtype": "f32",
}


def expdist(a, b, sa, sb, config: dict | None = None,
            interpret: bool = False):
    """``a``/``b``: (2, K) localizations; ``sa``/``sb``: (K,) uncertainties
    -> scalar Gaussian-overlap distance."""
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return expdist_pallas(a, b, sa, sb, interpret=interpret, **cfg)
