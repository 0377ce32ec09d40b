"""Dedispersion search space + cost features (gather-bound)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


class DedispProblem(KernelProblem):
    kernel_name = "dedisp"
    # ARTS-like scale, reduced x8 in T to keep full-space studies tractable
    default_shape = {"c": 1536, "d": 2048, "t_out": 4096}
    dtype = jnp.float32

    @property
    def _t_in(self) -> int:
        # max delay at the lowest frequency for the largest DM, plus t_out
        return self.shape["t_out"] + 8192

    def build_space(self) -> SearchSpace:
        def vmem_ok(c: Config) -> bool:
            tc = c["time_chunk"] or self.shape["t_out"]
            ws = (c["block_c"] * self._t_in * 4
                  + 2 * c["block_d"] * self.shape["t_out"] * 4
                  + 2 * tc * 4)
            return ws <= PORTABLE_VMEM   # no double-buffer margin: acc-heavy

        params = [
            Param("block_d", (8, 16, 32, 64, 128, 256, 512)),
            Param("block_c", (1, 2, 4, 8, 16, 32, 64)),
            # chunks larger than t_out are dead rows (space audit): 0
            # already means "whole t_out", so trim the menu to the shape
            Param("time_chunk", tuple(v for v in (0, 256, 512, 1024,
                                                  2048, 4096, 8192)
                                      if v <= self.shape["t_out"])),
            Param("unroll_d", (1, 2, 4, 8)),
            Param("acc_dtype", ("f32", "bf16")),
        ]
        def vmem_ok_vec(c: dict) -> np.ndarray:
            tc = np.where(c["time_chunk"] == 0, self.shape["t_out"],
                          c["time_chunk"])
            ws = (c["block_c"] * self._t_in * 4
                  + 2 * c["block_d"] * self.shape["t_out"] * 4
                  + 2 * tc * 4)
            return ws <= PORTABLE_VMEM

        constraints = [
            Constraint("unroll_divides", lambda c: c["block_d"] % c["unroll_d"] == 0,
                       vec=lambda c: c["block_d"] % c["unroll_d"] == 0),
            Constraint("chunk_le_t", lambda c: c["time_chunk"]
                       <= self.shape["t_out"],
                       vec=lambda c: c["time_chunk"] <= self.shape["t_out"]),
            Constraint("vmem", vmem_ok, vec=vmem_ok_vec),
        ]
        return SearchSpace(params, constraints, name="dedisp")

    def features(self, c: Config, arch: str) -> KernelFeatures:
        cc, dd, t_out = (self.shape[k] for k in ("c", "d", "t_out"))
        bd, bc = c["block_d"], c["block_c"]
        gd, gc = cdiv(dd, bd), cdiv(cc, bc)
        tc = c["time_chunk"] or t_out
        acc_b = 4 if c["acc_dtype"] == "f32" else 2

        adds = float(cc) * dd * t_out
        vpu = adds * (0.75 if c["acc_dtype"] == "bf16" else 1.0)
        # unaligned lane-dim dynamic slices: each (c,d) row read is a shifted
        # copy — misaligned vector loads run at a fraction of peak
        gather = float(gd) * cc * t_out * 4.0      # x re-read per d-block
        hbm = gather * 0.0 + (gd * gc * bc * self._t_in * 4.0  # staged tiles
                              + dd * t_out * 4.0)              # output
        ws = (bc * self._t_in * 4.0 + 2 * bd * t_out * acc_b + 2 * tc * 4.0)

        # scalar-prefetch shift lookups stall issue between rows; deeper
        # unrolling hides part of the latency
        serialization = min(0.5, 0.15 / c["unroll_d"] + 0.1 / max(1, bc))
        return KernelFeatures(
            vpu_flops=vpu,
            hbm_bytes=hbm,
            gather_bytes=float(cc) * dd * t_out * 4.0 / max(1, bd),
            vmem_working_set=ws,
            grid_steps=float(gd * gc),
            dtype_bytes=acc_b,
            lane_extent=min(tc, t_out),
            sublane_extent=bd,
            unroll=c["unroll_d"],
            inner_trip=bd,
            serialization=serialization,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        cc, dd, t_out = (self.shape[k] for k in ("c", "d", "t_out"))
        bd, bc = c["block_d"], c["block_c"]
        gd, gc = -(-dd // bd), -(-cc // bc)
        tc = np.where(c["time_chunk"] == 0, t_out, c["time_chunk"])
        acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)

        adds = float(cc) * dd * t_out
        vpu = np.where(c["acc_dtype"] == "bf16", adds * 0.75, adds * 1.0)
        gather = gd.astype(np.float64) * cc * t_out * 4.0
        hbm = gather * 0.0 + (gd * gc * bc * self._t_in * 4.0
                              + dd * t_out * 4.0)
        ws = (bc * self._t_in * 4.0 + 2 * bd * t_out * acc_b + 2 * tc * 4.0)
        serialization = np.minimum(0.5, 0.15 / c["unroll_d"]
                                   + 0.1 / np.maximum(1, bc))

        return FeatureBatch.from_columns(
            len(bd),
            vpu_flops=vpu,
            hbm_bytes=hbm,
            gather_bytes=float(cc) * dd * t_out * 4.0 / np.maximum(1, bd),
            vmem_working_set=ws,
            grid_steps=gd * gc,
            dtype_bytes=acc_b,
            lane_extent=np.minimum(tc, t_out),
            sublane_extent=bd,
            unroll=c["unroll_d"],
            inner_trip=bd,
            serialization=serialization,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        if small:
            cc, dd, t_out, t_in = 12, 24, 160, 416
        else:
            cc, dd, t_out, t_in = (self.shape["c"], self.shape["d"],
                                   self.shape["t_out"], self._t_in)
        x = jax.random.normal(key, (cc, t_in), self.dtype)
        delays = ref.make_delays(cc, dd, dm_step=0.05 if small else 1.0)
        delays = jnp.minimum(delays, t_in - t_out)
        return {"x": x, "delays": delays, "t_out": t_out}

    def run_reference(self, config: Config, inputs: dict):
        return ref.dedisp_reference(inputs["x"], inputs["delays"],
                                    inputs["t_out"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        return kernel.dedisp(inputs["x"], inputs["delays"],
                             t_out=inputs["t_out"], interpret=interpret,
                             **config)
