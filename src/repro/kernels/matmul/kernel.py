"""Tunable Pallas TPU GEMM: C = alpha*A@B + beta*C.

TPU adaptation of the CLBlast GEMM parameters (see DESIGN.md §2):

  block_m/block_n/block_k — BlockSpec tile shape (MWG/NWG/KWG),
  unroll_k               — the k-block is consumed as ``unroll_k`` sub-dots
                            (issue-granularity / VREG-pressure control),
  grid_order             — "mn" (n fastest) or "nm" (m fastest): which
                            operand enjoys VMEM residency across the grid,
  split_k                — k-dimension split into independent partial-sum
                            products combined outside (FlashDecoding-style),
  acc_dtype              — f32 (exact) or bf16 (halves accumulator VMEM),
  rhs_layout             — "kn" (B is (K,N)) or "nk" (B stored transposed;
                            contraction runs over B's lane dim instead).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _gemm_kernel(a_ref, b_ref, cin_ref, out_ref, acc_ref, *,
                 alpha, beta, unroll_k, rhs_layout, acc_dtype, nk_grid):
    """One (bm, bn) output tile; k is the innermost (sequential) grid axis."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    bk = a.shape[1]
    step = bk // unroll_k
    acc = acc_ref[...].astype(jnp.float32)
    for u in range(unroll_k):          # static unroll: issue-granularity knob
        a_u = a[:, u * step:(u + 1) * step]
        if rhs_layout == "kn":
            b_u = b[u * step:(u + 1) * step, :]
            part = jax.lax.dot_general(
                a_u, b_u, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:                          # B block is (bn, bk): contract lane dim
            b_u = b[:, u * step:(u + 1) * step]
            part = jax.lax.dot_general(
                a_u, b_u, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc = acc + part
    acc_ref[...] = acc.astype(acc_ref.dtype)

    @pl.when(k_idx == nk_grid - 1)
    def _finish():
        res = alpha * acc_ref[...].astype(jnp.float32)
        if beta != 0.0:
            res = res + beta * cin_ref[...].astype(jnp.float32)
        out_ref[...] = res.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "unroll_k",
                     "grid_order", "split_k", "acc_dtype", "rhs_layout",
                     "alpha", "beta", "interpret"))
def gemm(a, b, c, *, block_m=128, block_n=128, block_k=512, unroll_k=1,
         grid_order="mn", split_k=1, acc_dtype="f32", rhs_layout="kn",
         alpha=1.0, beta=1.0, interpret=False):
    """Tunable GEMM.  ``a``: (M,K); ``b``: (K,N) if rhs_layout=="kn" else
    (N,K); ``c``: (M,N).  Shapes must be multiples of the block sizes
    (the wrapper pads otherwise)."""
    m, k = a.shape
    n = c.shape[1]
    acc_jnp = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16

    def one_slice(a_s, b_s, beta_s):
        k_s = a_s.shape[1]
        nk = cdiv(k_s, block_k)
        kern = functools.partial(
            _gemm_kernel, alpha=alpha, beta=beta_s, unroll_k=unroll_k,
            rhs_layout=rhs_layout, acc_dtype=acc_dtype, nk_grid=nk)
        if rhs_layout == "kn":
            b_spec = pl.BlockSpec((block_k, block_n), lambda *g: (g[2], g[1]))
        else:
            b_spec = pl.BlockSpec((block_n, block_k), lambda *g: (g[1], g[2]))
        grid = (cdiv(m, block_m), cdiv(n, block_n), nk)
        if grid_order == "nm":          # m varies fastest instead of n
            grid = (grid[1], grid[0], grid[2])
            swap = lambda f: (lambda i, j, kk: f(j, i, kk))
        else:
            swap = lambda f: f
        return pl.pallas_call(
            kern,
            name="gemm",
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), swap(lambda i, j, kk: (i, kk))),
                pl.BlockSpec(b_spec.block_shape, swap(b_spec.index_map)),
                pl.BlockSpec((block_m, block_n), swap(lambda i, j, kk: (i, j))),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   swap(lambda i, j, kk: (i, j))),
            out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_jnp)],
            interpret=interpret,
        )(a_s, b_s, c)

    if split_k == 1:
        return one_slice(a, b, beta)
    # split-k: independent partial GEMMs over k slices, summed outside.
    ks = k // split_k
    parts = []
    for s in range(split_k):
        a_s = jax.lax.slice_in_dim(a, s * ks, (s + 1) * ks, axis=1)
        if rhs_layout == "kn":
            b_s = jax.lax.slice_in_dim(b, s * ks, (s + 1) * ks, axis=0)
        else:
            b_s = jax.lax.slice_in_dim(b, s * ks, (s + 1) * ks, axis=1)
        parts.append(one_slice(a_s, b_s, 0.0).astype(jnp.float32))
    out = sum(parts)
    if beta != 0.0:
        out = out + beta * c.astype(jnp.float32)
    return out.astype(c.dtype)
