"""Public MLA decode op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import mla_decode as mla_pallas

# the best of a measured random-search session (32 configs) on a TPU v5e at
# DeepSeek-V2's widths, 64 sequences of 4k-64k cached tokens (PERF.md)
DEFAULT_CONFIG = {"block_kv": 2048, "block_h": 128, "acc_dtype": "f32"}


def mla_decode(q_lat, q_pe, ckv, k_pe, lengths, *, scale,
               config: dict | None = None, interpret: bool = False):
    """``q_lat``: (B, H, C); ``q_pe``: (B, H, R); ``ckv``: (B, T, C);
    ``k_pe``: (B, T, R); ``lengths``: (B,) int32 -> ``o_lat`` (B, H, C)."""
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return mla_pallas(q_lat, q_pe, ckv, k_pe, lengths, scale=scale,
                      interpret=interpret, **cfg)
