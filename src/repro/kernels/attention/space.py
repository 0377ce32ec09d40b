"""Flash-attention tunable problem — ties the suite to the LM stack."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


class AttentionProblem(KernelProblem):
    kernel_name = "flash_attention"
    default_shape = {"hq": 32, "hkv": 8, "tq": 4096, "tk": 4096, "d": 128}
    dtype = jnp.bfloat16

    def build_space(self) -> SearchSpace:
        d = self.shape["d"]
        g = self.shape["hq"] // self.shape["hkv"]

        def ws_bytes(c: Config) -> float:
            bq, bkv, bh = c["block_q"], c["block_kv"], c["block_h"]
            acc_b = 4 if c["acc_dtype"] == "f32" else 2
            return (bh * bq * d * 2 + 2 * bkv * d * 2     # q tile + k,v tiles
                    + bh * bq * bkv * 4 * 2               # s, p
                    + bh * bq * d * acc_b + 2 * bh * bq * 4)

        params = [
            Param("block_q", (64, 128, 256, 512, 1024)),
            Param("block_kv", (128, 256, 512, 1024, 2048)),
            # menu trimmed to this shape's GQA group: block_h values that
            # can never satisfy gqa_group are dead rows (space audit)
            Param("block_h", tuple(v for v in (1, 2, 4, 8)
                                   if v <= g and g % v == 0)),
            Param("skip_masked", (0, 1)),
            Param("acc_dtype", ("f32", "bf16")),
        ]
        def ws_bytes_vec(c: dict):
            bq, bkv, bh = c["block_q"], c["block_kv"], c["block_h"]
            acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
            return (bh * bq * d * 2 + 2 * bkv * d * 2
                    + bh * bq * bkv * 4 * 2
                    + bh * bq * d * acc_b + 2 * bh * bq * 4)

        constraints = [
            Constraint("fits", lambda c: c["block_q"] <= self.shape["tq"]
                       and c["block_kv"] <= self.shape["tk"],
                       vec=lambda c: (c["block_q"] <= self.shape["tq"])
                       & (c["block_kv"] <= self.shape["tk"])),
            Constraint("gqa_group", lambda c: c["block_h"] <= g
                       and g % c["block_h"] == 0,
                       vec=lambda c: (c["block_h"] <= g)
                       & (g % c["block_h"] == 0)),
            Constraint("vmem", lambda c: 2 * ws_bytes(c) <= PORTABLE_VMEM,
                       vec=lambda c: 2 * ws_bytes_vec(c) <= PORTABLE_VMEM),
        ]
        return SearchSpace(params, constraints, name="flash_attention")

    def features(self, c: Config, arch: str) -> KernelFeatures:
        hq, hkv, tq, tk, d = (self.shape[k]
                              for k in ("hq", "hkv", "tq", "tk", "d"))
        bq, bkv = min(c["block_q"], tq), min(c["block_kv"], tk)
        bh = c["block_h"]
        gq, gkv = cdiv(tq, bq), cdiv(tk, bkv)
        # causal: with block skipping only ~half the kv tiles compute;
        # without it every visited tile does the full (masked) matmul.
        frac = 0.55 if c["skip_masked"] else 1.0
        mxu = 4.0 * hq * tq * tk * d * frac
        vpu = 6.0 * hq * tq * tk * frac
        trans = 1.0 * hq * tq * tk * frac
        # block_h amortizes k/v streaming across the GQA group
        kv_reads = (hq / bh) * gq * tk * d * 2 * 2
        hbm = hq * tq * d * 2 * 2 + kv_reads
        acc_b = 4 if c["acc_dtype"] == "f32" else 2
        ws = (bh * bq * d * 2 + 2 * bkv * d * 2 + bh * bq * bkv * 4 * 2
              + bh * bq * d * acc_b + 2 * bh * bq * 4)
        return KernelFeatures(
            mxu_flops=mxu, vpu_flops=vpu, transcendental_ops=trans,
            hbm_bytes=hbm, vmem_working_set=float(ws),
            grid_steps=float(hq / bh * gq * gkv),
            mxu_tile=(bq, bkv, d),
            dtype_bytes=2 if c["acc_dtype"] == "bf16" else 4,
            lane_extent=bkv, sublane_extent=bq,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        hq, hkv, tq, tk, d = (self.shape[k]
                              for k in ("hq", "hkv", "tq", "tk", "d"))
        bq = np.minimum(c["block_q"], tq)
        bkv = np.minimum(c["block_kv"], tk)
        bh = c["block_h"]
        gq, gkv = -(-tq // bq), -(-tk // bkv)
        frac = np.where(c["skip_masked"] == 1, 0.55, 1.0)
        mxu = 4.0 * hq * tq * tk * d * frac
        vpu = 6.0 * hq * tq * tk * frac
        trans = 1.0 * hq * tq * tk * frac
        kv_reads = (hq / bh) * gq * tk * d * 2 * 2
        hbm = hq * tq * d * 2 * 2 + kv_reads
        acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
        ws = (bh * bq * d * 2 + 2 * bkv * d * 2 + bh * bq * bkv * 4 * 2
              + bh * bq * d * acc_b + 2 * bh * bq * 4)
        return FeatureBatch.from_columns(
            len(bq),
            mxu_flops=mxu, vpu_flops=vpu, transcendental_ops=trans,
            hbm_bytes=hbm, vmem_working_set=ws,
            grid_steps=hq / bh * gq * gkv,
            tile_m=np.maximum(1, bq), tile_n=np.maximum(1, bkv),
            tile_k=max(1, d),
            dtype_bytes=np.where(c["acc_dtype"] == "bf16", 2, 4),
            lane_extent=bkv, sublane_extent=bq,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        if small:
            hq, hkv, tq, tk, d = 4, 2, 256, 256, 64
        else:
            hq, hkv, tq, tk, d = (self.shape[k]
                                  for k in ("hq", "hkv", "tq", "tk", "d"))
        kq, kk, kv = jax.random.split(key, 3)
        return {
            "q": jax.random.normal(kq, (hq, tq, d), self.dtype),
            "k": jax.random.normal(kk, (hkv, tk, d), self.dtype),
            "v": jax.random.normal(kv, (hkv, tk, d), self.dtype),
            "causal": True,
        }

    def run_reference(self, config: Config, inputs: dict):
        return ref.mha_reference(inputs["q"], inputs["k"], inputs["v"],
                                 causal=inputs["causal"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        return kernel.flash_attention(inputs["q"], inputs["k"], inputs["v"],
                                      causal=inputs["causal"],
                                      interpret=interpret, **config)
