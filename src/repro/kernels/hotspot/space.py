"""Hotspot search space + cost features.

Objective: full simulation of ``n_total`` sweeps — ceil(n_total/tt) launches.
Temporal tiling trades redundant halo compute against HBM round-trips, which
is exactly what produces the paper's Hotspot outlier (a >10x-over-median
cluster of deeply-temporal-tiled configs in an otherwise memory-bound
landscape).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


class HotspotProblem(KernelProblem):
    kernel_name = "hotspot"
    default_shape = {"h": 2048, "w": 2048, "n_total": 600}
    dtype = jnp.float32

    def build_space(self) -> SearchSpace:
        def vmem_ok(c: Config) -> bool:
            th = c["block_h"] + 2 * c["tt"]
            tw = c["block_w"] + 2 * c["tt"]
            acc_b = 4 if c["acc_dtype"] == "f32" else 2
            ws = th * tw * (4 + 4 + 2 * acc_b) + c["block_h"] * c["block_w"] * 4
            return 2 * ws <= PORTABLE_VMEM

        params = [
            # like the paper's Hotspot space, block_w deliberately includes
            # lane-starved widths (8..64) — the landscape must contain the
            # bad region for the "cluster >10x over median" claim to mean
            # anything
            Param("block_h", (8, 16, 32, 64, 128, 256)),
            Param("block_w", (8, 16, 32, 64, 128, 256, 512, 1024)),
            Param("tt", tuple(range(1, 11))),
            Param("unroll_t", tuple(range(1, 11))),
            Param("keep_power_vmem", (0, 1)),
            Param("acc_dtype", ("f32", "bf16")),
            Param("grid_order", ("rm", "cm")),
        ]
        def vmem_ok_vec(c: dict) -> np.ndarray:
            th = c["block_h"] + 2 * c["tt"]
            tw = c["block_w"] + 2 * c["tt"]
            acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
            ws = th * tw * (4 + 4 + 2 * acc_b) + c["block_h"] * c["block_w"] * 4
            return 2 * ws <= PORTABLE_VMEM

        constraints = [
            Constraint("unroll_divides_tt", lambda c: c["tt"] % c["unroll_t"] == 0,
                       vec=lambda c: c["tt"] % c["unroll_t"] == 0),
            Constraint("vmem", vmem_ok, vec=vmem_ok_vec),
            Constraint("halo_sane", lambda c: 2 * c["tt"] <= c["block_h"] + 8,
                       vec=lambda c: 2 * c["tt"] <= c["block_h"] + 8),
        ]
        return SearchSpace(params, constraints, name="hotspot")

    def features(self, c: Config, arch: str) -> KernelFeatures:
        h, w, n_total = (self.shape[k] for k in ("h", "w", "n_total"))
        bh, bw, tt = c["block_h"], c["block_w"], c["tt"]
        gh, gw = cdiv(h, bh), cdiv(w, bw)
        th, tw = bh + 2 * tt, bw + 2 * tt
        acc_b = 4 if c["acc_dtype"] == "f32" else 2
        launches = cdiv(n_total, tt)

        # per launch: stencil is ~12 VPU flops/cell/sweep over the full tile
        vpu_launch = 12.0 * gh * gw * th * tw * tt
        if c["acc_dtype"] == "bf16":
            vpu_launch *= 0.75
        # per launch HBM: temp+power tiles materialized (write+read) + output
        tile_bytes = gh * gw * th * tw * 4.0
        power_stream = tile_bytes if c["keep_power_vmem"] else tile_bytes * max(1, tt // 2)
        hbm_launch = (h * w * 8.0            # temp+power source reads
                      + 2.0 * tile_bytes     # temp tiles write+read
                      + 2.0 * power_stream   # power tiles
                      + gh * gw * bh * bw * 4.0)
        ws = th * tw * (4.0 + (4.0 if c["keep_power_vmem"] else 0.0)
                        + 2.0 * acc_b) + bh * bw * 4.0
        # column-major traversal strides across the tile array: poorer DMA
        # locality on the materialized (gh*gw, th, tw) layout
        serialization = 0.08 if c["grid_order"] == "cm" else 0.0

        return KernelFeatures(
            vpu_flops=vpu_launch * launches,
            hbm_bytes=hbm_launch * launches,
            vmem_working_set=float(ws),
            grid_steps=float(gh * gw * launches),
            dtype_bytes=4,
            lane_extent=min(bw, w),
            sublane_extent=min(bh, h),
            unroll=c["unroll_t"],
            inner_trip=tt,
            serialization=serialization,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        h, w, n_total = (self.shape[k] for k in ("h", "w", "n_total"))
        bh, bw, tt = c["block_h"], c["block_w"], c["tt"]
        gh, gw = -(-h // bh), -(-w // bw)
        th, tw = bh + 2 * tt, bw + 2 * tt
        acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
        launches = -(-n_total // tt)

        vpu_launch = 12.0 * gh * gw * th * tw * tt
        vpu_launch = np.where(c["acc_dtype"] == "bf16",
                              vpu_launch * 0.75, vpu_launch)
        tile_bytes = gh * gw * th * tw * 4.0
        power_stream = np.where(c["keep_power_vmem"] == 1, tile_bytes,
                                tile_bytes * np.maximum(1, tt // 2))
        hbm_launch = (h * w * 8.0
                      + 2.0 * tile_bytes
                      + 2.0 * power_stream
                      + gh * gw * bh * bw * 4.0)
        ws = th * tw * (4.0 + np.where(c["keep_power_vmem"] == 1, 4.0, 0.0)
                        + 2.0 * acc_b) + bh * bw * 4.0
        serialization = np.where(c["grid_order"] == "cm", 0.08, 0.0)

        return FeatureBatch.from_columns(
            len(bh),
            vpu_flops=vpu_launch * launches,
            hbm_bytes=hbm_launch * launches,
            vmem_working_set=ws,
            grid_steps=gh * gw * launches,
            dtype_bytes=4,
            lane_extent=np.minimum(bw, w),
            sublane_extent=np.minimum(bh, h),
            unroll=c["unroll_t"],
            inner_trip=tt,
            serialization=serialization,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        if small:
            h, w, n = 40, 136, 4
        else:
            h, w, n = self.shape["h"], self.shape["w"], self.shape["n_total"]
        k1, k2 = jax.random.split(key)
        # pre-padded domain (pad >= n_total); compare central crop only
        hp, wp = h + 2 * n, w + 2 * n
        return {"temp": 60 + 20 * jax.random.uniform(k1, (hp, wp), self.dtype),
                "power": jax.random.uniform(k2, (hp, wp), self.dtype),
                "n_sweeps": n, "crop": n}

    def run_reference(self, config: Config, inputs: dict):
        out = ref.hotspot_reference(inputs["temp"], inputs["power"],
                                    inputs["n_sweeps"])
        c = inputs["crop"]
        return out[c:-c, c:-c]

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        cfg = {k: config[k] for k in
               ("tt", "block_h", "block_w", "unroll_t", "acc_dtype",
                "keep_power_vmem", "grid_order")}
        out = kernel.hotspot(inputs["temp"], inputs["power"],
                             inputs["n_sweeps"], interpret=interpret, **cfg)
        c = inputs["crop"]
        return out[c:-c, c:-c]
