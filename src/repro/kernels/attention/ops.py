"""Public flash-attention op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import flash_attention as flash_pallas

#: ``block_h`` 8 asks for the whole GQA group: the kernel takes the largest
#: divisor of ``Hq // Hkv`` not above it (7 at a group of 7, 4 at 4), so
#: each K/V tile is streamed once for all the query heads it serves.
#: ``block_q`` 128 / ``block_kv`` 1024: the fastest tile of a chip sweep at
#: DeepSeek-Coder-33B's 8k prefill (group 7) that the v5e compiler also
#: takes at a group of 8; wider tiles outgrow its VMEM there.
DEFAULT_CONFIG = {"block_q": 128, "block_kv": 1024, "block_h": 8,
                  "skip_masked": 1, "acc_dtype": "f32"}


def attention(q, k, v, *, causal=True, scale=None, config: dict | None = None,
              interpret: bool = False):
    """``q``: (Hq, Tq, D); ``k``/``v``: (Hkv, Tk, D) -> (Hq, Tq, D)."""
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    return flash_pallas(q, k, v, causal=causal, scale=scale,
                        interpret=interpret, **cfg)
