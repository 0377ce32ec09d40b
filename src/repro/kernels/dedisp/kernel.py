"""Tunable Pallas TPU dedispersion kernel.

TPU adaptation of the AMBER/BAT dedispersion parameters: the CUDA kernel's
per-thread sample/DM tiling becomes a (block_d × T_out) output tile per grid
program with the channel dimension as the sequential accumulation axis;
per-(channel, DM) shifts are *scalar-prefetched* (SMEM) and applied as
dynamic lane-dimension slices — the TPU replacement for the GPU's gather
through texture/L2.  ``block_c`` channels are staged per grid step;
``time_chunk`` bounds VREG pressure; ``unroll_d`` unrolls the DM row loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _dedisp_kernel(delay_ref, x_ref, out_ref, acc_ref, *, block_d, block_c,
                   t_out, time_chunk, unroll_d, acc_dtype, nc_grid):
    c_idx = pl.program_id(1)
    adt = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16

    @pl.when(c_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d0 = pl.program_id(0) * block_d
    tc = time_chunk if time_chunk else t_out

    def add_row(d, acc):
        """Accumulate one DM row across the staged channels."""
        row = acc
        for cc in range(block_c):
            ch = c_idx * block_c + cc
            shift = delay_ref[ch, d0 + d]
            for t0 in range(0, t_out, tc):
                w = min(tc, t_out - t0)
                seg = lax.dynamic_slice(
                    x_ref[cc], (shift + t0,), (w,)).astype(adt)
                row = lax.dynamic_update_slice(
                    row, (lax.dynamic_slice(row, (t0,), (w,)) + seg), (t0,))
        return row

    n_chunks = block_d // unroll_d

    def d_chunk(dc, _):
        for du in range(unroll_d):
            d = dc * unroll_d + du
            acc_ref[d, :] = add_row(d, acc_ref[d, :])
        return 0

    if n_chunks > 1:
        lax.fori_loop(0, n_chunks, d_chunk, 0)
    else:
        d_chunk(0, 0)

    @pl.when(c_idx == nc_grid - 1)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("t_out", "block_d", "block_c", "time_chunk", "unroll_d",
                     "acc_dtype", "interpret"))
def dedisp(x, delays, *, t_out, block_d=32, block_c=4, time_chunk=0,
           unroll_d=1, acc_dtype="f32", interpret=False):
    """``x``: (C, T); ``delays``: (C, D) int32.  Returns (D, t_out) f32.
    Requires max(delays) + t_out <= T."""
    c_dim, t = x.shape
    d_dim = delays.shape[1]
    bd = min(block_d, d_dim)
    bc = min(block_c, c_dim)
    gd, gc = cdiv(d_dim, bd), cdiv(c_dim, bc)
    # pad D to a block multiple (delay table repeats the last DM; harmless,
    # the padded rows are cropped from the output)
    dp = gd * bd
    if dp != d_dim:
        delays = jnp.pad(delays, ((0, 0), (0, dp - d_dim)), mode="edge")
    cp = gc * bc
    if cp != c_dim:
        x = jnp.pad(x, ((0, cp - c_dim), (0, 0)))
        delays = jnp.pad(delays, ((0, cp - c_dim), (0, 0)))

    ud = max(1, min(unroll_d, bd))
    while bd % ud:
        ud -= 1
    kern = functools.partial(
        _dedisp_kernel, block_d=bd, block_c=bc, t_out=t_out,
        time_chunk=time_chunk, unroll_d=ud, acc_dtype=acc_dtype, nc_grid=gc)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gd, gc),
        in_specs=[pl.BlockSpec((bc, t), lambda i, c, delay_ref: (c, 0))],
        out_specs=pl.BlockSpec((bd, t_out), lambda i, c, delay_ref: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bd, t_out), jnp.float32
                                   if acc_dtype == "f32" else jnp.bfloat16)],
    )
    out = pl.pallas_call(
        kern,
        name="dedisp",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((dp, t_out), jnp.float32),
        interpret=interpret,
    )(delays, x)
    return out[:d_dim]
