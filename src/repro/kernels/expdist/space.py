"""ExpDist search space + cost features."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


class ExpdistProblem(KernelProblem):
    kernel_name = "expdist"
    default_shape = {"ka": 65536, "kb": 65536}
    dtype = jnp.float32

    def build_space(self) -> SearchSpace:
        def vmem_ok(c: Config) -> bool:
            bi, bj = c["block_i"], c["block_j"]
            cb = 4 if c["compute_dtype"] == "f32" else 2
            inter = 5 * bi * (bj // c["unroll_j"]) * cb
            ws = 3 * bi * 4 + 3 * bj * 4 + inter + c["n_y_blocks"] * 4
            return 2 * ws <= PORTABLE_VMEM

        bj_vals = (128, 256, 512, 1024, 2048)
        # n_y_blocks beyond the largest possible j-grid (smallest block_j)
        # can never satisfy njb_le_grid: dead rows (space audit)
        max_grid = cdiv(self.shape["kb"], min(bj_vals))
        params = [
            Param("block_i", (8, 16, 32, 64, 128, 256, 512)),
            Param("block_j", bj_vals),
            Param("use_column", (0, 1)),
            Param("n_y_blocks", tuple(v for v in (1, 2, 4, 8, 16, 32, 64,
                                                  128, 256, 512, 1024)
                                      if v <= max_grid)),
            Param("unroll_j", (1, 2, 4)),
            Param("exp_variant", ("exp", "exp2")),
            Param("compute_dtype", ("f32", "bf16")),
        ]
        def vmem_ok_vec(c: dict) -> np.ndarray:
            bi, bj = c["block_i"], c["block_j"]
            cb = np.where(c["compute_dtype"] == "f32", 4, 2)
            inter = 5 * bi * (bj // c["unroll_j"]) * cb
            ws = 3 * bi * 4 + 3 * bj * 4 + inter + c["n_y_blocks"] * 4
            return 2 * ws <= PORTABLE_VMEM

        constraints = [
            Constraint("column_implies_single",
                       lambda c: not c["use_column"] or c["n_y_blocks"] == 1,
                       vec=lambda c: (c["use_column"] == 0)
                       | (c["n_y_blocks"] == 1)),
            Constraint("unroll_chunks", lambda c: c["block_j"]
                       % c["unroll_j"] == 0
                       and c["block_j"] // c["unroll_j"] >= 128,
                       vec=lambda c: (c["block_j"] % c["unroll_j"] == 0)
                       & (c["block_j"] // c["unroll_j"] >= 128)),
            Constraint("njb_le_grid", lambda c: c["n_y_blocks"]
                       <= cdiv(self.shape["kb"], c["block_j"]),
                       vec=lambda c: c["n_y_blocks"]
                       <= -(-self.shape["kb"] // c["block_j"])),
            Constraint("vmem", vmem_ok, vec=vmem_ok_vec),
        ]
        return SearchSpace(params, constraints, name="expdist")

    def features(self, c: Config, arch: str) -> KernelFeatures:
        ka, kb = self.shape["ka"], self.shape["kb"]
        bi, bj = c["block_i"], c["block_j"]
        gi, gj = cdiv(ka, bi), cdiv(kb, bj)
        cb = 4 if c["compute_dtype"] == "f32" else 2
        pairs = float(ka) * kb

        vpu = 10.0 * pairs
        if c["compute_dtype"] == "bf16":
            vpu *= 0.75
        # exp2 is the native VPU op; exp pays the ln2 scaling inside
        trans = pairs * (1.0 if c["exp_variant"] == "exp2" else 1.25)

        hbm = (gi * gj * bj * 3 * 4        # b tiles per (i, j)
               + gi * bi * 3 * 4           # a tiles resident over j
               + gi * c["n_y_blocks"] * 4)
        inter = 5 * bi * (bj // c["unroll_j"]) * cb
        ws = 3 * bi * 4 + 3 * bj * 4 + inter + c["n_y_blocks"] * 4
        # scalar accumulate into the partial column serializes slightly more
        # for wider partial layouts
        serialization = 0.02 if c["use_column"] else 0.04

        return KernelFeatures(
            vpu_flops=vpu,
            transcendental_ops=trans,
            hbm_bytes=hbm,
            vmem_working_set=float(ws),
            grid_steps=float(gi * gj),
            dtype_bytes=cb,
            lane_extent=bj // c["unroll_j"],
            sublane_extent=min(bi, ka),
            unroll=c["unroll_j"],
            inner_trip=c["unroll_j"],
            serialization=serialization,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        ka, kb = self.shape["ka"], self.shape["kb"]
        bi, bj = c["block_i"], c["block_j"]
        gi, gj = -(-ka // bi), -(-kb // bj)
        cb = np.where(c["compute_dtype"] == "f32", 4, 2)
        pairs = float(ka) * kb

        base = 10.0 * pairs
        vpu = np.where(c["compute_dtype"] == "bf16", base * 0.75, base)
        trans = np.where(c["exp_variant"] == "exp2",
                         pairs * 1.0, pairs * 1.25)

        hbm = (gi * gj * bj * 3 * 4
               + gi * bi * 3 * 4
               + gi * c["n_y_blocks"] * 4)
        inter = 5 * bi * (bj // c["unroll_j"]) * cb
        ws = 3 * bi * 4 + 3 * bj * 4 + inter + c["n_y_blocks"] * 4
        serialization = np.where(c["use_column"] == 1, 0.02, 0.04)

        return FeatureBatch.from_columns(
            len(bi),
            vpu_flops=vpu,
            transcendental_ops=trans,
            hbm_bytes=hbm,
            vmem_working_set=ws,
            grid_steps=gi * gj,
            dtype_bytes=cb,
            lane_extent=bj // c["unroll_j"],
            sublane_extent=np.minimum(bi, ka),
            unroll=c["unroll_j"],
            inner_trip=c["unroll_j"],
            serialization=serialization,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        ka, kb = (384, 320) if small else (self.shape["ka"], self.shape["kb"])
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "a": jax.random.normal(k1, (2, ka), self.dtype),
            "b": jax.random.normal(k2, (2, kb), self.dtype),
            "sa": jax.random.uniform(k3, (ka,), self.dtype, 0.5, 1.5),
            "sb": jax.random.uniform(k4, (kb,), self.dtype, 0.5, 1.5),
        }

    def run_reference(self, config: Config, inputs: dict):
        return ref.expdist_reference(inputs["a"], inputs["b"],
                                     inputs["sa"], inputs["sb"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        return kernel.expdist(inputs["a"], inputs["b"], inputs["sa"],
                              inputs["sb"], interpret=interpret, **config)
