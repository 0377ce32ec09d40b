"""N-body search space + cost features (compute-bound, like the paper's)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


class NbodyProblem(KernelProblem):
    kernel_name = "nbody"
    default_shape = {"n": 131072}
    dtype = jnp.float32

    def build_space(self) -> SearchSpace:
        n = self.shape["n"]

        def vmem_ok(c: Config) -> bool:
            bi, bj = c["block_i"], c["block_j"]
            cb = 4 if c["compute_dtype"] == "f32" else 2
            # xi/xj/mass tiles + ~6 (bi, bj/unroll) intermediates
            inter = 6 * bi * (bj // c["unroll_j"]) * cb
            ws = 4 * bi * 4 + 4 * bj * 4 + bj * 4 + inter + 3 * bi * 4
            return 2 * ws <= PORTABLE_VMEM

        params = [
            Param("block_i", (8, 16, 32, 64, 128, 256, 512)),
            Param("block_j", (128, 256, 512, 1024, 2048)),
            Param("layout", ("soa", "aos")),
            Param("unroll_j", (1, 2, 4, 8)),
            Param("rsqrt_method", ("exact", "approx")),
            Param("compute_dtype", ("f32", "bf16")),
        ]
        def vmem_ok_vec(c: dict) -> np.ndarray:
            bi, bj = c["block_i"], c["block_j"]
            cb = np.where(c["compute_dtype"] == "f32", 4, 2)
            inter = 6 * bi * (bj // c["unroll_j"]) * cb
            ws = 4 * bi * 4 + 4 * bj * 4 + bj * 4 + inter + 3 * bi * 4
            return 2 * ws <= PORTABLE_VMEM

        constraints = [
            Constraint("blocks_fit_n", lambda c: c["block_i"] <= n
                       and c["block_j"] <= n,
                       vec=lambda c: (c["block_i"] <= n) & (c["block_j"] <= n)),
            Constraint("unroll_chunks", lambda c: c["block_j"]
                       % c["unroll_j"] == 0
                       and c["block_j"] // c["unroll_j"] >= 128,
                       vec=lambda c: (c["block_j"] % c["unroll_j"] == 0)
                       & (c["block_j"] // c["unroll_j"] >= 128)),
            Constraint("vmem", vmem_ok, vec=vmem_ok_vec),
        ]
        return SearchSpace(params, constraints, name="nbody")

    def features(self, c: Config, arch: str) -> KernelFeatures:
        n = self.shape["n"]
        bi, bj = c["block_i"], c["block_j"]
        gi, gj = cdiv(n, bi), cdiv(n, bj)
        cb = 4 if c["compute_dtype"] == "f32" else 2

        # ~14 VPU flops + 1 transcendental (rsqrt/sqrt+div) per pair
        pairs = float(n) * n
        vpu = 14.0 * pairs
        if c["compute_dtype"] == "bf16":
            vpu *= 0.75
        trans = pairs * (1.0 if c["rsqrt_method"] == "approx" else 2.0)
        if c["rsqrt_method"] == "approx":
            vpu += 3.0 * pairs                 # Newton refinement

        # xi re-streamed per j step, xj per grid step (Mosaic keeps the
        # consecutive-j xi block resident: only gj fresh xi fetches per row)
        aosf = 4 / 3 if c["layout"] == "aos" else 1.0    # padded w component
        hbm = (gi * gj * bj * 4 * 4 * aosf     # xj + mass tiles
               + gi * bi * 4 * 4 * aosf        # xi per i-row (resident over j)
               + n * 3 * 4)                    # output
        inter = 6 * bi * (bj // c["unroll_j"]) * cb
        ws = 4 * bi * 4 + 4 * bj * 4 + bj * 4 + inter + 3 * bi * 4

        # AoS (bi,4) tiles force a Mosaic relayout before the vector math —
        # modeled as a lane-utilization floor (not a raw 4/128 penalty)
        lane = bj // c["unroll_j"] if c["layout"] == "soa" else 32
        return KernelFeatures(
            vpu_flops=vpu,
            transcendental_ops=trans,
            hbm_bytes=hbm,
            vmem_working_set=float(ws),
            grid_steps=float(gi * gj),
            dtype_bytes=cb,
            lane_extent=lane,
            sublane_extent=min(bi, n),
            unroll=c["unroll_j"],
            inner_trip=c["unroll_j"],
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        n = self.shape["n"]
        bi, bj = c["block_i"], c["block_j"]
        gi, gj = -(-n // bi), -(-n // bj)
        cb = np.where(c["compute_dtype"] == "f32", 4, 2)

        pairs = float(n) * n
        base = 14.0 * pairs
        vpu = np.where(c["compute_dtype"] == "bf16", base * 0.75, base)
        approx = c["rsqrt_method"] == "approx"
        trans = np.where(approx, pairs * 1.0, pairs * 2.0)
        vpu = vpu + np.where(approx, 3.0 * pairs, 0.0)

        aosf = np.where(c["layout"] == "aos", 4 / 3, 1.0)
        hbm = (gi * gj * bj * 4 * 4 * aosf
               + gi * bi * 4 * 4 * aosf
               + n * 3 * 4)
        inter = 6 * bi * (bj // c["unroll_j"]) * cb
        ws = 4 * bi * 4 + 4 * bj * 4 + bj * 4 + inter + 3 * bi * 4

        lane = np.where(c["layout"] == "soa", bj // c["unroll_j"], 32)
        return FeatureBatch.from_columns(
            len(bi),
            vpu_flops=vpu,
            transcendental_ops=trans,
            hbm_bytes=hbm,
            vmem_working_set=ws,
            grid_steps=gi * gj,
            dtype_bytes=cb,
            lane_extent=lane,
            sublane_extent=np.minimum(bi, n),
            unroll=c["unroll_j"],
            inner_trip=c["unroll_j"],
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        n = 512 if small else self.shape["n"]
        k1, k2 = jax.random.split(key)
        return {"pos": jax.random.normal(k1, (3, n), self.dtype),
                "mass": jax.random.uniform(k2, (n,), self.dtype,
                                           minval=0.5, maxval=1.5)}

    def run_reference(self, config: Config, inputs: dict):
        return ref.nbody_reference(inputs["pos"], inputs["mass"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        return kernel.nbody(inputs["pos"], inputs["mass"],
                            interpret=interpret, **config)
