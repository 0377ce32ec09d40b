"""Tunable Pallas TPU ExpDist kernel (quadratic Gaussian-overlap reduction).

TPU adaptation of the BAT ExpDist parameters: thread blocks → (block_i ×
block_j) interaction tiles; ``use_column``/``n_y_blocks`` → split-reduction
layout: with ``use_column=1`` the j grid axis accumulates sequentially in
VMEM scratch (one partial per i block); with ``use_column=0`` partials are
scattered over ``n_y_blocks`` columns and combined outside (the TPU
equivalent of the CUDA column-block reduction);  ``exp_variant`` trades
``exp`` against ``exp2``-with-scaling (different transcendental mix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv

LOG2E = 1.4426950408889634


def _expdist_kernel(a_ref, sa_ref, b_ref, sb_ref, out_ref, acc_ref, *,
                    unroll_j, exp_variant, compute_dtype, n_y_blocks,
                    nj_grid):
    j_idx = pl.program_id(1)
    cdt = jnp.float32 if compute_dtype == "f32" else jnp.bfloat16

    @pl.when(j_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ax = a_ref[0:1, :].astype(cdt)           # (1, bi)
    ay = a_ref[1:2, :].astype(cdt)
    sa2 = (sa_ref[0:1, :] * sa_ref[0:1, :]).astype(jnp.float32)

    bj = b_ref.shape[1]
    step = bj // unroll_j
    total = jnp.zeros((), jnp.float32)
    for u in range(unroll_j):
        sl = slice(u * step, (u + 1) * step)
        bx = b_ref[0:1, sl].astype(cdt)
        by = b_ref[1:2, sl].astype(cdt)
        sb2 = (sb_ref[0:1, sl] * sb_ref[0:1, sl]).astype(jnp.float32)
        dx = (ax.T - bx).astype(jnp.float32)  # (bi, step)
        dy = (ay.T - by).astype(jnp.float32)
        r2 = dx * dx + dy * dy
        denom = 2.0 * (sa2.T + sb2)
        z = -r2 / denom
        if exp_variant == "exp":
            e = jnp.exp(z)
        else:
            e = jnp.exp2(z * LOG2E)
        total = total + e.sum()

    col = j_idx % n_y_blocks
    acc_ref[0, col] += total

    @pl.when(j_idx == nj_grid - 1)
    def _finish():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_i", "block_j", "use_column", "n_y_blocks",
                     "unroll_j", "exp_variant", "compute_dtype", "interpret"))
def expdist(a, b, sa, sb, *, block_i=128, block_j=512, use_column=1,
            n_y_blocks=1, unroll_j=1, exp_variant="exp",
            compute_dtype="f32", interpret=False):
    """``a``/``b``: (2, K); ``sa``/``sb``: (K,).  Returns scalar f32."""
    bi = min(block_i, a.shape[1])
    bj = min(block_j, b.shape[1])

    def pad_far(pts, sig, mult, far):
        """Pad to a block multiple with far-away points (exp underflows to
        exactly 0, so padding never contributes).  ``a`` and ``b`` pad to
        *opposite* corners — otherwise pad×pad pairs sit at distance 0 and
        each contributes exp(0)=1."""
        kk = pts.shape[1]
        kp = cdiv(kk, mult) * mult
        if kp == kk:
            return pts, sig
        return (jnp.pad(pts, ((0, 0), (0, kp - kk)), constant_values=far),
                jnp.pad(sig, (0, kp - kk), constant_values=1.0))

    a, sa = pad_far(a, sa, bi, +1e9)
    b, sb = pad_far(b, sb, bj, -1e9)
    ka, kb = a.shape[1], b.shape[1]
    gi, gj = ka // bi, kb // bj
    njb = 1 if use_column else max(1, min(n_y_blocks, gj))

    uj = max(1, min(unroll_j, bj))
    while bj % uj:
        uj -= 1
    kern = functools.partial(
        _expdist_kernel, unroll_j=uj, exp_variant=exp_variant,
        compute_dtype=compute_dtype, n_y_blocks=njb, nj_grid=gj)

    partials = pl.pallas_call(
        kern,
        name="expdist",
        grid=(gi, gj),
        in_specs=[
            pl.BlockSpec((2, bi), lambda i, j: (0, i)),
            pl.BlockSpec((1, bi), lambda i, j: (0, i)),
            pl.BlockSpec((2, bj), lambda i, j: (0, j)),
            pl.BlockSpec((1, bj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, njb), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((gi, njb), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, njb), jnp.float32)],
        interpret=interpret,
    )(a, sa.reshape(1, ka), b, sb.reshape(1, kb))
    return partials.sum()
