"""Public dedispersion op (radio-astronomy transient pipeline)."""

from __future__ import annotations

from .kernel import dedisp as dedisp_pallas

DEFAULT_CONFIG = {
    "block_d": 64, "block_c": 4, "time_chunk": 0, "unroll_d": 1,
    "acc_dtype": "f32",
}


def dedisp(x, delays, t_out: int, config: dict | None = None,
           interpret: bool = False):
    """``x``: (C, T) channel samples; ``delays``: (C, D) int32 per-channel
    per-DM delays -> (D, t_out) dedispersed series."""
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return dedisp_pallas(x, delays, t_out=t_out, interpret=interpret, **cfg)
