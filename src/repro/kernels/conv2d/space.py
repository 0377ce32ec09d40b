"""Conv2D search space + cost features (van Werkhoven conv analogue).

Cardinality 6·6·4·4·4·2·2 = 18 432 — matching the paper's Convolution space.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


class Conv2dProblem(KernelProblem):
    kernel_name = "conv2d"
    default_shape = {"h": 4096, "w": 4096, "fh": 15, "fw": 15}
    dtype = jnp.float32

    def build_space(self) -> SearchSpace:
        h, w = self.shape["h"], self.shape["w"]
        fh, fw = self.shape["fh"], self.shape["fw"]

        def vmem_ok(c: Config) -> bool:
            th = c["block_h"] + fh - 1
            tw = c["block_w"] + fw - 1
            acc_b = 4 if c["acc_dtype"] == "f32" else 2
            rows = c["row_chunk"] or c["block_h"]
            ws = (th * tw * 4 + c["block_h"] * c["block_w"] * 4
                  + rows * c["block_w"] * acc_b + fh * fw * 4)
            return 2 * ws <= PORTABLE_VMEM

        params = [
            Param("block_h", (8, 16, 32, 64, 128, 256)),
            Param("block_w", (128, 256, 512, 1024, 2048, 4096)),
            Param("unroll_fh", (1, 3, 5, 15)),
            Param("unroll_fw", (1, 3, 5, 15)),
            Param("row_chunk", (0, 8, 16, 32)),
            Param("acc_dtype", ("f32", "bf16")),
            Param("filter_smem", (0, 1)),
        ]
        def vmem_ok_vec(c: dict) -> np.ndarray:
            th = c["block_h"] + fh - 1
            tw = c["block_w"] + fw - 1
            acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
            rows = np.where(c["row_chunk"] == 0, c["block_h"], c["row_chunk"])
            ws = (th * tw * 4 + c["block_h"] * c["block_w"] * 4
                  + rows * c["block_w"] * acc_b + fh * fw * 4)
            return 2 * ws <= PORTABLE_VMEM

        constraints = [
            Constraint("fits_shape", lambda c: c["block_h"] <= h
                       and c["block_w"] <= w,
                       vec=lambda c: (c["block_h"] <= h) & (c["block_w"] <= w)),
            Constraint("unroll_divides", lambda c: fh % c["unroll_fh"] == 0
                       and fw % c["unroll_fw"] == 0,
                       vec=lambda c: (fh % c["unroll_fh"] == 0)
                       & (fw % c["unroll_fw"] == 0)),
            Constraint("row_chunk_divides",
                       lambda c: c["row_chunk"] == 0
                       or c["block_h"] % c["row_chunk"] == 0,
                       vec=lambda c: (c["row_chunk"] == 0)
                       | (c["block_h"] % np.maximum(c["row_chunk"], 1) == 0)),
            Constraint("vmem", vmem_ok, vec=vmem_ok_vec),
        ]
        return SearchSpace(params, constraints, name="conv2d")

    def features(self, c: Config, arch: str) -> KernelFeatures:
        h, w = self.shape["h"], self.shape["w"]
        fh, fw = self.shape["fh"], self.shape["fw"]
        oh, ow = h - fh + 1, w - fw + 1
        bh, bw = min(c["block_h"], oh), min(c["block_w"], ow)
        gh, gw = cdiv(oh, bh), cdiv(ow, bw)
        th, tw = bh + fh - 1, bw + fw - 1
        acc_b = 4 if c["acc_dtype"] == "f32" else 2
        rows = c["row_chunk"] or bh

        # halo materialization: input read + tiles write + tiles read
        tile_bytes = gh * gw * th * tw * 4.0
        hbm = h * w * 4.0 + 2.0 * tile_bytes + gh * gw * bh * bw * 4.0
        ws = th * tw * 4.0 + bh * bw * 4.0 + rows * bw * acc_b + fh * fw * 4.0

        vpu = 2.0 * oh * ow * fh * fw
        if c["acc_dtype"] == "bf16":
            vpu *= 0.75        # bf16 VPU packing gain ... and accuracy loss
        # dynamic scalar filter loads from VMEM stall the vector pipe a bit;
        # SMEM scalar fetch overlaps (the read-only-cache analogue)
        serialization = 0.05 if not c["filter_smem"] else 0.0
        # row chunking controls VREG pressure: too-large accumulators spill
        spill = 1.0 if rows * bw * acc_b <= 64 * 1024 else 1.3
        vpu *= spill

        u = c["unroll_fh"] * c["unroll_fw"]
        return KernelFeatures(
            vpu_flops=vpu,
            hbm_bytes=hbm,
            vmem_working_set=ws,
            grid_steps=float(gh * gw),
            dtype_bytes=4,
            lane_extent=bw,
            sublane_extent=rows,
            unroll=u,
            inner_trip=fh * fw,
            serialization=serialization,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        h, w = self.shape["h"], self.shape["w"]
        fh, fw = self.shape["fh"], self.shape["fw"]
        oh, ow = h - fh + 1, w - fw + 1
        bh = np.minimum(c["block_h"], oh)
        bw = np.minimum(c["block_w"], ow)
        gh, gw = -(-oh // bh), -(-ow // bw)
        th, tw = bh + fh - 1, bw + fw - 1
        acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
        rows = np.where(c["row_chunk"] == 0, bh, c["row_chunk"])

        tile_bytes = gh * gw * th * tw * 4.0
        hbm = h * w * 4.0 + 2.0 * tile_bytes + gh * gw * bh * bw * 4.0
        ws = th * tw * 4.0 + bh * bw * 4.0 + rows * bw * acc_b + fh * fw * 4.0

        base = 2.0 * oh * ow * fh * fw
        vpu = np.where(c["acc_dtype"] == "bf16", base * 0.75, base)
        serialization = np.where(c["filter_smem"] == 0, 0.05, 0.0)
        spill = np.where(rows * bw * acc_b <= 64 * 1024, 1.0, 1.3)
        vpu = vpu * spill

        return FeatureBatch.from_columns(
            len(bh),
            vpu_flops=vpu,
            hbm_bytes=hbm,
            vmem_working_set=ws,
            grid_steps=gh * gw,
            dtype_bytes=4,
            lane_extent=bw,
            sublane_extent=rows,
            unroll=c["unroll_fh"] * c["unroll_fw"],
            inner_trip=fh * fw,
            serialization=serialization,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        if small:
            h, w, fh, fw = 48, 160, 5, 5
        else:
            h, w = self.shape["h"], self.shape["w"]
            fh, fw = self.shape["fh"], self.shape["fw"]
        k1, k2 = jax.random.split(key)
        return {"image": jax.random.normal(k1, (h, w), self.dtype),
                "filt": jax.random.normal(k2, (fh, fw), self.dtype)}

    def run_reference(self, config: Config, inputs: dict):
        return ref.conv2d_reference(inputs["image"], inputs["filt"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        cfg = dict(config)
        cfg["filter_smem"] = bool(cfg.get("filter_smem", 0))
        return kernel.conv2d(inputs["image"], inputs["filt"],
                             interpret=interpret, **cfg)
