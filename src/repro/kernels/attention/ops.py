"""Public flash-attention op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import flash_attention as flash_pallas

DEFAULT_CONFIG = {"block_q": 256, "block_kv": 512, "block_h": 4,
                  "skip_masked": 1, "acc_dtype": "f32"}


def attention(q, k, v, *, causal=True, scale=None, config: dict | None = None,
              interpret: bool = False):
    """``q``: (Hq, Tq, D); ``k``/``v``: (Hkv, Tk, D) -> (Hq, Tq, D)."""
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return flash_pallas(q, k, v, causal=causal, scale=scale,
                        interpret=interpret, **cfg)
