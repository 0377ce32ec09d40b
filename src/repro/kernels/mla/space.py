"""MLA decode tunable problem: DeepSeek-V2's absorbed latent attention, one
query token per sequence over a ragged batch of latent caches."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.costmodel import FeatureBatch, KernelFeatures
from ...core.space import Config, Constraint, Param, SearchSpace
from ..common import PORTABLE_VMEM, KernelProblem, cdiv
from . import kernel, ref


def log_uniform_lengths(b: int, lo: int, hi: int, seed: int = 0) -> tuple:
    """``b`` cache lengths drawn log-uniform over [lo, hi) by
    ``numpy.random.default_rng(seed)``, truncated to integers."""
    rng = np.random.default_rng(seed)
    return tuple(int(n) for n in
                 np.exp(rng.uniform(np.log(lo), np.log(hi), b)).astype(int))


class MLADecodeProblem(KernelProblem):
    kernel_name = "mla_decode"
    #: DeepSeek-V2: 128 heads, kv_lora 512, rope 64, yarn softmax scale;
    #: 64 sequences with caches of 4k-64k tokens in a 64k-token buffer
    default_shape = {"b": 64, "h": 128, "c": 512, "r": 64, "t_max": 65536,
                     "lengths": log_uniform_lengths(64, 4096, 65536),
                     "scale": 0.1147214}
    dtype = jnp.bfloat16

    def build_space(self) -> SearchSpace:
        h, c, r = self.shape["h"], self.shape["c"], self.shape["r"]

        def ws_bytes(bkv, bh, acc_b):
            return (bh * (c + r) * 2 + bkv * (c + r) * 2   # q, cache tiles
                    + bh * bkv * 4 * 2                     # s, p
                    + bh * c * acc_b + 2 * bh * 4)         # acc, m, l

        params = [
            Param("block_kv", (128, 256, 512, 1024, 2048)),
            Param("block_h", tuple(v for v in (8, 16, 32, 64, 128)
                                   if h % v == 0)),
            Param("acc_dtype", ("f32", "bf16")),
        ]
        constraints = [
            Constraint("fits", lambda cf: cf["block_kv"] <= self.shape["t_max"],
                       vec=lambda cf: cf["block_kv"] <= self.shape["t_max"]),
            Constraint(
                "vmem",
                lambda cf: 2 * ws_bytes(
                    cf["block_kv"], cf["block_h"],
                    4 if cf["acc_dtype"] == "f32" else 2) <= PORTABLE_VMEM,
                vec=lambda cf: 2 * ws_bytes(
                    cf["block_kv"], cf["block_h"],
                    np.where(cf["acc_dtype"] == "f32", 4, 2)) <= PORTABLE_VMEM),
        ]
        return SearchSpace(params, constraints, name="mla_decode")

    def _live_tiles(self, bkv):
        """Cache tiles that hold a live token, summed over the sequences."""
        lengths = np.asarray(self.shape["lengths"], dtype=np.int64)
        return (-(-lengths[None, :] // np.reshape(bkv, (-1, 1)))).sum(axis=1)

    def features(self, c: Config, arch: str) -> KernelFeatures:
        b, h, cw, r, t = (self.shape[k] for k in ("b", "h", "c", "r", "t_max"))
        bkv, bh = min(c["block_kv"], t), c["block_h"]
        live = int(self._live_tiles(bkv)[0])
        # only live tiles compute; each is read once per group of bh heads
        mxu = 2.0 * h * (2 * cw + r) * bkv * live
        vpu = 6.0 * h * bkv * live
        trans = 1.0 * h * bkv * live
        hbm = (h / bh) * live * bkv * (cw + r) * 2 + b * h * (2 * cw + r) * 2
        acc_b = 4 if c["acc_dtype"] == "f32" else 2
        ws = (bh * (cw + r) * 2 + bkv * (cw + r) * 2 + bh * bkv * 4 * 2
              + bh * cw * acc_b + 2 * bh * 4)
        return KernelFeatures(
            mxu_flops=mxu, vpu_flops=vpu, transcendental_ops=trans,
            hbm_bytes=hbm, vmem_working_set=float(ws),
            grid_steps=float(b * (h / bh) * cdiv(t, bkv)),
            mxu_tile=(bh, bkv, cw),
            dtype_bytes=2 if c["acc_dtype"] == "bf16" else 4,
            lane_extent=bkv, sublane_extent=bh,
        )

    def feature_columns(self, c: dict, arch: str) -> FeatureBatch:
        """Vectorized :meth:`features` over value columns (bit-identical)."""
        b, h, cw, r, t = (self.shape[k] for k in ("b", "h", "c", "r", "t_max"))
        bkv = np.minimum(c["block_kv"], t)
        bh = c["block_h"]
        live = self._live_tiles(bkv)
        mxu = 2.0 * h * (2 * cw + r) * bkv * live
        vpu = 6.0 * h * bkv * live
        trans = 1.0 * h * bkv * live
        hbm = (h / bh) * live * bkv * (cw + r) * 2 + b * h * (2 * cw + r) * 2
        acc_b = np.where(c["acc_dtype"] == "f32", 4, 2)
        ws = (bh * (cw + r) * 2 + bkv * (cw + r) * 2 + bh * bkv * 4 * 2
              + bh * cw * acc_b + 2 * bh * 4)
        return FeatureBatch.from_columns(
            len(bkv),
            mxu_flops=mxu, vpu_flops=vpu, transcendental_ops=trans,
            hbm_bytes=hbm, vmem_working_set=ws,
            grid_steps=b * (h / bh) * (-(-t // bkv)),
            tile_m=np.maximum(1, bh), tile_n=np.maximum(1, bkv),
            tile_k=max(1, cw),
            dtype_bytes=np.where(c["acc_dtype"] == "bf16", 2, 4),
            lane_extent=bkv, sublane_extent=bh,
        )

    # -- correctness hooks ------------------------------------------------ #
    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        if small:
            b, h, c, r, t = 3, 16, 128, 64, 512
            lengths = (1, 300, 512)
            scale = (128 + 64) ** -0.5
        else:
            b, h, c, r, t = (self.shape[k]
                             for k in ("b", "h", "c", "r", "t_max"))
            lengths, scale = self.shape["lengths"], self.shape["scale"]
        kq, kp, kc, kk = jax.random.split(key, 4)
        return {
            "q_lat": jax.random.normal(kq, (b, h, c), self.dtype),
            "q_pe": jax.random.normal(kp, (b, h, r), self.dtype),
            "ckv": jax.random.normal(kc, (b, t, c), self.dtype),
            "k_pe": jax.random.normal(kk, (b, t, r), self.dtype),
            "lengths": jnp.asarray(lengths, jnp.int32),
            "scale": float(scale),
        }

    def run_reference(self, config: Config, inputs: dict):
        return ref.mla_decode_reference(
            inputs["q_lat"], inputs["q_pe"], inputs["ckv"], inputs["k_pe"],
            inputs["lengths"], scale=inputs["scale"])

    def run_kernel(self, config: Config, inputs: dict, *, interpret: bool):
        return kernel.mla_decode(
            inputs["q_lat"], inputs["q_pe"], inputs["ckv"], inputs["k_pe"],
            inputs["lengths"], scale=inputs["scale"], interpret=interpret,
            **config)
