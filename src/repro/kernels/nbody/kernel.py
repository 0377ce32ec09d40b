"""Tunable Pallas TPU N-body kernel (all-pairs gravitational forces).

TPU adaptation of the KTT/CUDA-SDK N-body parameters: thread-block size →
(block_i × block_j) interaction tile; ``use_soa`` → (3,N) SoA (lane dim = N,
full 128-lane utilization) vs (N,4) AoS (4/128 lanes — the faithful
re-reading of the AoS penalty); inner unroll → block_j consumed in
``unroll_j`` sub-chunks; ``local_mem`` → j-bodies staged per grid step via
BlockSpec (always VMEM on TPU — the tunable is tile residency shape);
rsqrt variant → exact ``1/sqrt`` vs ``lax.rsqrt`` + one Newton step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv
from .ref import EPS2, G


def _inv_r3(r2, method):
    r2 = r2.astype(jnp.float32)
    if method == "exact":
        inv = 1.0 / jnp.sqrt(r2)
    else:
        y = lax.rsqrt(r2)
        y = y * (1.5 - 0.5 * r2 * y * y)        # one Newton refinement
        inv = y
    return inv * inv * inv


def _nbody_kernel(xi_ref, xj_ref, mj_ref, out_ref, acc_ref, *,
                  layout, unroll_j, rsqrt_method, compute_dtype, eps2,
                  nj_grid):
    j_idx = pl.program_id(1)
    cdt = jnp.float32 if compute_dtype == "f32" else jnp.bfloat16

    @pl.when(j_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if layout == "soa":
        xi = xi_ref[...].astype(cdt)          # (3, bi)
        xj = xj_ref[...].astype(cdt)          # (3, bj)
        mj = mj_ref[...].astype(jnp.float32)  # (1, bj)
    else:
        xi = xi_ref[...].T[:3].astype(cdt)    # (bi,4) -> (3, bi)
        xj = xj_ref[...].T[:3].astype(cdt)
        mj = xj_ref[...].T[3:4].astype(jnp.float32)   # mass packed as w

    bj = xj.shape[1]
    step = bj // unroll_j
    acc = acc_ref[...]
    for u in range(unroll_j):
        sl = slice(u * step, (u + 1) * step)
        dx = (xj[0:1, sl] - xi[0:1, :].T).astype(jnp.float32)  # (bi, step)
        dy = (xj[1:2, sl] - xi[1:2, :].T).astype(jnp.float32)
        dz = (xj[2:3, sl] - xi[2:3, :].T).astype(jnp.float32)
        r2 = dx * dx + dy * dy + dz * dz + eps2
        w = mj[0:1, sl] * _inv_r3(r2, rsqrt_method)
        fx = (dx * w).sum(axis=1)             # (bi,)
        fy = (dy * w).sum(axis=1)
        fz = (dz * w).sum(axis=1)
        acc = acc + jnp.stack([fx, fy, fz], axis=0)
    acc_ref[...] = acc

    @pl.when(j_idx == nj_grid - 1)
    def _finish():
        out_ref[...] = (G * acc_ref[...]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_i", "block_j", "layout", "unroll_j",
                     "rsqrt_method", "compute_dtype", "eps2", "interpret"))
def nbody(pos, mass, *, block_i=128, block_j=1024, layout="soa", unroll_j=1,
          rsqrt_method="exact", compute_dtype="f32", eps2=EPS2,
          interpret=False):
    """``pos``: (3, N) f32; ``mass``: (N,).  Returns (3, N) accelerations.
    N must be a multiple of block sizes (wrapper clamps for tests)."""
    n = pos.shape[1]
    bi, bj = min(block_i, n), min(block_j, n)
    gi, gj = cdiv(n, bi), cdiv(n, bj)

    uj = max(1, min(unroll_j, bj))
    while bj % uj:
        uj -= 1
    kern = functools.partial(
        _nbody_kernel, layout=layout, unroll_j=uj,
        rsqrt_method=rsqrt_method, compute_dtype=compute_dtype, eps2=eps2,
        nj_grid=gj)

    if layout == "soa":
        in_arrays = (pos, pos, mass.reshape(1, n))
        in_specs = [pl.BlockSpec((3, bi), lambda i, j: (0, i)),
                    pl.BlockSpec((3, bj), lambda i, j: (0, j)),
                    pl.BlockSpec((1, bj), lambda i, j: (0, j))]
    else:
        aos = jnp.concatenate([pos, mass.reshape(1, n)], axis=0).T  # (N, 4)
        in_arrays = (aos, aos, mass.reshape(1, n))
        in_specs = [pl.BlockSpec((bi, 4), lambda i, j: (i, 0)),
                    pl.BlockSpec((bj, 4), lambda i, j: (j, 0)),
                    pl.BlockSpec((1, bj), lambda i, j: (0, j))]

    out_spec = pl.BlockSpec((3, bi), lambda i, j: (0, i))
    grid = (gi, gj)
    out = pl.pallas_call(
        kern,
        name="nbody",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((3, gi * bi), pos.dtype),
        scratch_shapes=[pltpu.VMEM((3, bi), jnp.float32)],
        interpret=interpret,
    )(*in_arrays)
    return out[:, :n]
