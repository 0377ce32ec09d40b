"""Pipeline parallelism: GPipe schedule over a ``stage`` mesh axis.

The production mesh for the assigned scale is TPxFSDP (see DESIGN.md §6) —
PP is the optional third axis for scaling past a pod's HBM without growing
TP (e.g. trillion-parameter variants on 4+ pods).  This module provides the
schedule as a composable ``shard_map`` transform:

* each stage's parameters live on one slice of the ``stage`` axis
  (stacked leading axis, sharded over ``stage``),
* activations flow stage-to-stage with ``jax.lax.ppermute`` — on hardware
  this is neighbor-only ICI traffic, the cheapest collective there is,
* microbatches fill the pipe GPipe-style: ``n_ticks = n_micro + n_stages-1``;
  bubble fraction = (n_stages-1)/n_ticks, amortized by more microbatches.

The schedule runs the *same* compiled stage body every tick on every stage
(SPMD), with masked reads/writes at the pipe head/tail — no per-stage
programs, so it scales to any stage count with one HLO.

``pipeline_apply`` is forward-only composable (jax.grad differentiates
through it; ppermute has a transpose rule, so the backward pass is the
reverse pipeline automatically).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stage_permutation(n_stages: int) -> list[tuple[int, int]]:
    """Ring i -> i+1 (the wrap link carries garbage that is masked off)."""
    return [(i, (i + 1) % n_stages) for i in range(n_stages)]


def pipeline_apply(stage_fn: Callable, mesh: Mesh, *, axis: str = "stage",
                   n_microbatches: int | None = None):
    """Wrap ``stage_fn(stage_params, x) -> y`` into a GPipe pipeline.

    Returns ``apply(stacked_params, x)`` where ``stacked_params`` leaves have
    a leading ``n_stages`` axis (sharded over ``axis``) and ``x`` is
    ``(n_micro, mb, ...)`` microbatched input (replicated or batch-sharded on
    other axes).  Output matches ``x``'s shape with ``stage_fn`` applied by
    all stages in sequence.
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    perm = stage_permutation(n_stages)

    def per_stage(params, x):
        # params: this stage's slice, leading axis 1; x: (n_micro, mb, ...)
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        n_micro = x.shape[0]
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros(x.shape[1:], x.dtype)          # inter-stage register
        out = jnp.zeros_like(x)

        def tick(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t while t < n_micro; other stages
            # consume what arrived over the permute link last tick.
            inject = x[jnp.minimum(t, n_micro - 1)]
            xin = jnp.where(stage == 0, inject, buf)
            y = stage_fn(params, xin)
            # the last stage has produced microbatch t-(n_stages-1)
            mb_done = t - (n_stages - 1)
            write = jnp.logical_and(stage == n_stages - 1, mb_done >= 0)
            out = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(mb_done, 0), 0),
                lambda o: o, out)
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(tick, (buf, out),
                                     jnp.arange(n_ticks))
        # results live on the last stage; broadcast so every stage returns
        # the same value (psum over the one-hot mask).
        mask = (stage == n_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    in_specs = (P(axis), P())      # params stacked over stage; x replicated
    out_specs = P()
    f = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)

    def apply(stacked_params, x):
        if x.shape[0] % 1:
            raise ValueError("x must be (n_micro, mb, ...)")
        return f(stacked_params, x)

    return apply


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead: idle / total stage-ticks."""
    ticks = n_microbatches + n_stages - 1
    return (n_stages - 1) / ticks
