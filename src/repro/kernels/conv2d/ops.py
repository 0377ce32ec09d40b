"""Public conv2d op: the Pallas kernel with tuned-config defaults."""

from __future__ import annotations

from .kernel import conv2d as conv2d_pallas

DEFAULT_CONFIG = {
    "block_h": 64, "block_w": 1024, "unroll_fh": 5, "unroll_fw": 5,
    "row_chunk": 0, "acc_dtype": "f32", "filter_smem": True,
}


def conv2d(image, filt, config: dict | None = None,
           interpret: bool = False):
    cfg = {**DEFAULT_CONFIG, **(config or {})}
    cfg["filter_smem"] = bool(cfg["filter_smem"])
    return conv2d_pallas(image, filt, interpret=interpret, **cfg)
