"""Public hotspot op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import hotspot as hotspot_pallas

DEFAULT_CONFIG = {
    "tt": 6, "block_h": 64, "block_w": 512, "unroll_t": 2,
    "acc_dtype": "f32", "keep_power_vmem": 1, "grid_order": "rm",
}


def hotspot(temp, power, n_sweeps: int, config: dict | None = None,
            interpret: bool = False):
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return hotspot_pallas(temp, power, n_sweeps, interpret=interpret, **cfg)
