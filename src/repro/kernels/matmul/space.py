"""GEMM search space + analytical cost features (CLBlast analogue)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, round_up
from . import kernel, ref


class GemmProblem(KernelProblem):
    kernel_name = "gemm"
    # paper-scale shape (CLBlast benchmarks tune 4096^3-class GEMMs)
    default_shape = {"m": 4096, "n": 4096, "k": 4096}
    dtype = jnp.bfloat16

    def build_space(self) -> SearchSpace:
        m, n, k = self.shape["m"], self.shape["n"], self.shape["k"]
        params = [
            Param("block_m", (16, 32, 64, 128, 256, 512, 1024, 2048)),
            Param("block_n", (64, 128, 256, 512, 1024, 2048)),
            Param("block_k", (128, 256, 512, 1024, 2048, 4096)),
            Param("unroll_k", (1, 2, 4, 8)),
            Param("grid_order", ("mn", "nm")),
            Param("split_k", (1, 2, 4, 8)),
            Param("acc_dtype", ("f32", "bf16")),
            Param("rhs_layout", ("kn", "nk")),
        ]
        ab = 2  # bf16 operands

        def vmem_ok(c: Config) -> bool:
            acc_b = 4 if c["acc_dtype"] == "f32" else 2
            ws = (c["block_m"] * c["block_k"] * ab
                  + c["block_k"] * c["block_n"] * ab
                  + c["block_m"] * c["block_n"] * (acc_b + ab + ab))
            return 2 * ws <= PORTABLE_VMEM      # double-buffered fit

        # vectorized forms (CompiledSpace column protocol) of the same
        # predicates — elementwise-identical by the spacetable property tests
        def vmem_ok_vec(c: dict) -> np.ndarray:
            acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
            ws = (c["block_m"] * c["block_k"] * ab
                  + c["block_k"] * c["block_n"] * ab
                  + c["block_m"] * c["block_n"] * (acc_b + ab + ab))
            return 2 * ws <= PORTABLE_VMEM

        constraints = [
            Constraint("fits_shape", lambda c: c["block_m"] <= max(m, 8)
                       and c["block_n"] <= max(n, 128)
                       and c["split_k"] * c["block_k"] <= max(k, 128),
                       vec=lambda c: (c["block_m"] <= max(m, 8))
                       & (c["block_n"] <= max(n, 128))
                       & (c["split_k"] * c["block_k"] <= max(k, 128))),
            Constraint("unroll_divides", lambda c: c["block_k"] % c["unroll_k"] == 0
                       and c["block_k"] // c["unroll_k"] >= 128,
                       vec=lambda c: (c["block_k"] % c["unroll_k"] == 0)
                       & (c["block_k"] // c["unroll_k"] >= 128)),
            Constraint("vmem", vmem_ok, vec=vmem_ok_vec),
        ]
        return SearchSpace(params, constraints, name="gemm")

    # ------------------------------------------------------------------ #
    def features(self, c: Config, arch: str) -> KernelFeatures:
        m, n, k = self.shape["m"], self.shape["n"], self.shape["k"]
        bm, bn, bk = c["block_m"], c["block_n"], c["block_k"]
        sk, uk = c["split_k"], c["unroll_k"]
        ab = 2
        acc_b = 4 if c["acc_dtype"] == "f32" else 2

        mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk * sk)
        gm, gn, gk = mp // bm, np_ // bn, kp // (bk * sk)

        # HBM traffic (per k-split slice, all slices):
        a_traffic = mp * (kp // sk) * gn * ab
        b_traffic = (kp // sk) * np_ * gm * ab
        # grid-order residency: if a whole k-slice fits in one k step, the
        # operand indexed only by the *outer* axis stays VMEM-resident.
        if gk == 1:
            if c["grid_order"] == "mn":       # n fastest -> A(i,k) resident
                a_traffic = mp * (kp // sk) * ab
            else:                              # m fastest -> B(k,j) resident
                b_traffic = (kp // sk) * np_ * ab
        c_traffic = mp * np_ * ab * 2          # beta read + write
        # split-k partials round-trip through HBM in f32
        partial_traffic = sk * mp * np_ * 4 * 2 if sk > 1 else 0
        hbm = a_traffic + b_traffic + c_traffic + partial_traffic

        ws = (bm * bk * ab + bk * bn * ab + bm * bn * (acc_b + ab + ab))

        mxu_flops = 2.0 * m * n * k
        vpu = 2.0 * m * n                       # alpha/beta epilogue
        if c["rhs_layout"] == "nk":
            # contraction over B's lane dim: fine on MXU, but the (bn,bk)
            # load tiles are transposed relative to the output layout
            vpu += 0.5 * b_traffic / ab
        if sk > 1:
            vpu += (sk + 1.0) * m * n           # partial-sum combine

        return KernelFeatures(
            mxu_flops=mxu_flops,
            vpu_flops=vpu,
            hbm_bytes=float(hbm),
            vmem_working_set=float(ws),
            grid_steps=float(gm * gn * gk * sk),
            mxu_tile=(min(bm, m), min(bn, n), max(1, bk // uk)),
            dtype_bytes=ab,
            lane_extent=min(bn, n),
            sublane_extent=min(bm, m),
            unroll=uk,
            inner_trip=uk,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features`: the same expressions over value
        columns (int64 exact, float64 in the scalar operation order), so
        the batched cost model reproduces the per-config objectives bit for
        bit."""
        m, n, k = self.shape["m"], self.shape["n"], self.shape["k"]
        bm, bn, bk = c["block_m"], c["block_n"], c["block_k"]
        sk, uk = c["split_k"], c["unroll_k"]
        ab = 2
        acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)

        mp = -(-m // bm) * bm                  # round_up, columnwise
        np_ = -(-n // bn) * bn
        kp = -(-k // (bk * sk)) * (bk * sk)
        gm, gn, gk = mp // bm, np_ // bn, kp // (bk * sk)

        a_traffic = mp * (kp // sk) * gn * ab
        b_traffic = (kp // sk) * np_ * gm * ab
        order_mn = c["grid_order"] == "mn"
        a_traffic = np.where((gk == 1) & order_mn,
                             mp * (kp // sk) * ab, a_traffic)
        b_traffic = np.where((gk == 1) & ~order_mn,
                             (kp // sk) * np_ * ab, b_traffic)
        c_traffic = mp * np_ * ab * 2
        partial_traffic = np.where(sk > 1, sk * mp * np_ * 4 * 2, 0)
        hbm = a_traffic + b_traffic + c_traffic + partial_traffic

        ws = (bm * bk * ab + bk * bn * ab + bm * bn * (acc_b + ab + ab))

        vpu = np.full(len(bm), 2.0 * m * n)
        vpu = vpu + np.where(c["rhs_layout"] == "nk",
                             0.5 * b_traffic / ab, 0.0)
        vpu = vpu + np.where(sk > 1, (sk + 1.0) * m * n, 0.0)

        return FeatureBatch.from_columns(
            len(bm),
            mxu_flops=2.0 * m * n * k,
            vpu_flops=vpu,
            hbm_bytes=hbm,
            vmem_working_set=ws,
            grid_steps=gm * gn * gk * sk,
            tile_m=np.maximum(1, np.minimum(bm, m)),
            tile_n=np.maximum(1, np.minimum(bn, n)),
            tile_k=np.maximum(1, bk // uk),
            dtype_bytes=ab,
            lane_extent=np.minimum(bn, n),
            sublane_extent=np.minimum(bm, m),
            unroll=uk,
            inner_trip=uk,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        if small:
            m, n, k = 256, 256, 512
        else:
            m, n, k = self.shape["m"], self.shape["n"], self.shape["k"]
        ka, kb, kc = jax.random.split(key, 3)
        return {
            "a": jax.random.normal(ka, (m, k), self.dtype),
            "b": jax.random.normal(kb, (k, n), self.dtype),
            "c": jax.random.normal(kc, (m, n), self.dtype),
            "alpha": 0.75, "beta": 0.5,
        }

    def run_reference(self, config: Config, inputs: dict):
        return ref.gemm_reference(inputs["a"], inputs["b"], inputs["c"],
                                  inputs["alpha"], inputs["beta"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        a, b, c = inputs["a"], inputs["b"], inputs["c"]
        cfg = dict(config)
        m, k = a.shape
        n = c.shape[1]
        # clamp blocks to the (test-sized) problem
        cfg["block_m"] = min(cfg["block_m"], m)
        cfg["block_n"] = min(cfg["block_n"], n)
        ks = k // cfg["split_k"]
        cfg["block_k"] = min(cfg["block_k"], ks)
        if cfg["block_k"] % cfg["unroll_k"]:
            cfg["unroll_k"] = 1
        b_in = b if cfg["rhs_layout"] == "kn" else b.T
        return kernel.gemm(a, b_in, c, alpha=inputs["alpha"],
                           beta=inputs["beta"], interpret=interpret, **cfg)
