"""Public point-in-polygon op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import pnpoly as pnpoly_pallas

DEFAULT_CONFIG = {
    "block_points": 2048, "unroll_v": 4, "between_method": 0,
    "use_method": 0, "precompute_slope": 1, "coord_layout": "soa",
}


def pnpoly(points, poly, config: dict | None = None,
           interpret: bool = False):
    """``points``: (2, N); ``poly``: (2, V) -> int32 (1, N) inside flags."""
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return pnpoly_pallas(points, poly, interpret=interpret, **cfg)
