"""Pnpoly search space + cost features."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


class PnpolyProblem(KernelProblem):
    kernel_name = "pnpoly"
    default_shape = {"n": 2_000_000, "v": 600}
    dtype = jnp.float32

    def build_space(self) -> SearchSpace:
        v = self.shape["v"]
        params = [
            Param("block_points", (128, 256, 512, 1024, 2048, 4096)),
            Param("unroll_v", (1, 2, 3, 4, 6, 8)),
            Param("between_method", (0, 1, 2, 3)),
            Param("use_method", (0, 1, 2)),
            Param("precompute_slope", (0, 1)),
            Param("coord_layout", ("soa", "aos")),
        ]
        constraints = [
            Constraint("unroll_le_v", lambda c: c["unroll_v"] <= v,
                       vec=lambda c: c["unroll_v"] <= v),
            Constraint("vmem", lambda c: 2 * (2 * c["block_points"] * 4
                                              + 5 * v * 4
                                              + 6 * c["block_points"] * 4)
                       <= PORTABLE_VMEM,
                       vec=lambda c: 2 * (2 * c["block_points"] * 4
                                          + 5 * v * 4
                                          + 6 * c["block_points"] * 4)
                       <= PORTABLE_VMEM),
        ]
        return SearchSpace(params, constraints, name="pnpoly")

    def features(self, c: Config, arch: str) -> KernelFeatures:
        n, v = self.shape["n"], self.shape["v"]
        bp = c["block_points"]
        grid = cdiv(n, bp)
        # per edge per point: ~7 VPU ops (between variants differ slightly)
        per_edge = {0: 7.0, 1: 8.0, 2: 9.0, 3: 8.0}[c["between_method"]]
        per_edge += {0: 1.0, 1: 1.0, 2: 2.0}[c["use_method"]]
        if not c["precompute_slope"]:
            per_edge += 3.0                  # div + sub + select per edge
        vpu = per_edge * n * v
        pre = (5.0 * v) * grid if c["precompute_slope"] else 0.0
        vpu += pre

        hbm = 2.0 * n * 4 + n * 4 + 4 * v * 4 * 1.0   # points + out + poly
        ws = (2 * bp * 4 + 5 * v * 4 + 6 * bp * 4)
        # AoS forces a relayout; floor rather than raw 2/128 (see nbody)
        lane = bp if c["coord_layout"] == "soa" else 32
        sub = 8 if c["coord_layout"] == "soa" else bp
        # scalar edge loads from VMEM each iteration stall the vector pipe;
        # unrolling hides part of it
        serialization = 0.10 / c["unroll_v"]
        return KernelFeatures(
            vpu_flops=vpu,
            hbm_bytes=hbm,
            vmem_working_set=float(ws),
            grid_steps=float(grid),
            dtype_bytes=4,
            lane_extent=lane,
            sublane_extent=sub,
            unroll=c["unroll_v"],
            inner_trip=v,
            serialization=serialization,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        n, v = self.shape["n"], self.shape["v"]
        bp = c["block_points"]
        grid = -(-n // bp)
        # per-edge op counts: the method params' values (0..k) are the
        # lookup-table indices
        per_edge = np.array([7.0, 8.0, 9.0, 8.0])[c["between_method"]]
        per_edge = per_edge + np.array([1.0, 1.0, 2.0])[c["use_method"]]
        pre_off = c["precompute_slope"] == 0
        per_edge = per_edge + np.where(pre_off, 3.0, 0.0)
        vpu = per_edge * n * v
        pre = np.where(pre_off, 0.0, (5.0 * v) * grid)
        vpu = vpu + pre

        hbm = 2.0 * n * 4 + n * 4 + 4 * v * 4 * 1.0
        ws = (2 * bp * 4 + 5 * v * 4 + 6 * bp * 4)
        soa = c["coord_layout"] == "soa"
        lane = np.where(soa, bp, 32)
        sub = np.where(soa, 8, bp)
        serialization = 0.10 / c["unroll_v"]

        return FeatureBatch.from_columns(
            len(bp),
            vpu_flops=vpu,
            hbm_bytes=hbm,
            vmem_working_set=ws,
            grid_steps=grid,
            dtype_bytes=4,
            lane_extent=lane,
            sublane_extent=sub,
            unroll=c["unroll_v"],
            inner_trip=v,
            serialization=serialization,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        n, v = (1536, 17) if small else (self.shape["n"], self.shape["v"])
        k1, k2 = jax.random.split(key)
        # irregular star polygon (non-convex, no duplicate vertices)
        ang = jnp.sort(jax.random.uniform(k1, (v,), minval=0.0,
                                          maxval=2 * jnp.pi))
        rad = 0.4 + jax.random.uniform(k2, (v,), minval=0.0, maxval=0.6)
        poly = jnp.stack([rad * jnp.cos(ang), rad * jnp.sin(ang)])
        pts = jax.random.uniform(jax.random.fold_in(key, 7), (2, n),
                                 minval=-1.2, maxval=1.2)
        return {"points": pts.astype(self.dtype),
                "poly": poly.astype(self.dtype)}

    def run_reference(self, config: Config, inputs: dict):
        return ref.pnpoly_reference(inputs["points"], inputs["poly"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        out = kernel.pnpoly(inputs["points"], inputs["poly"],
                            interpret=interpret, **config)
        return out[0]
