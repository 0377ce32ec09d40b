"""Logical-axis sharding: map per-parameter logical names to mesh axes.

Parameters carry logical axis tuples (see models.layers).  Rules assign mesh
axes greedily with divisibility fallback — e.g. deepseek-coder's 56 heads
don't divide model=16, so TP falls through to the 128-wide head_dim.

Scheme ("FSDP × TP"):
  * ``model`` axis — tensor parallel: expert > vocab > ff > heads > kv_heads
    > lora > head_dim (first divisible wins)
  * ``data`` axis — ZeRO-3/FSDP: embed (d_model rows) or the largest
    remaining axis
  * ``pod`` axis — pure data parallel for params (replicated weights,
    gradient all-reduce crosses pods once per step)

Activation constraints are applied through :func:`constrain` (no-op without
an active mesh, so CPU unit tests are unaffected).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: contextvars.ContextVar[Mesh | None] = \
    contextvars.ContextVar("repro_mesh", default=None)

MODEL_PREFS = ("expert", "vocab", "ff", "heads", "heads_flat", "kv_heads",
               "q_lora", "kv_lora", "head_dim")
DATA_PREFS = ("embed", "ff", "vocab", "heads_flat", "q_lora", "kv_lora")


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    token = _MESH.set(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _MESH.reset(token)


def active_mesh() -> Mesh | None:
    return _MESH.get()


def axis_size(name: str = "model") -> int:
    """Extent of one mesh axis in the active mesh (1 without a mesh)."""
    mesh = _MESH.get()
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def constrain(x, *spec):
    """Sharding constraint by mesh-axis names; no-op without a mesh."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, tuple):
            clean.append(tuple(a for a in s if a in mesh.axis_names) or None)
        else:
            clean.append(s if s in mesh.axis_names else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*clean)))


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(shape: tuple[int, ...], logical: tuple, mesh: Mesh) -> P:
    """Greedy divisible assignment of mesh axes to logical axes."""
    sizes = _mesh_axis_sizes(mesh)
    assignment: dict[int, str | tuple] = {}

    def assign(mesh_axis: str, prefs) -> None:
        n = sizes.get(mesh_axis, 1)
        if n <= 1:
            return
        for name in prefs:
            for dim, lname in enumerate(logical):
                if lname == name and dim not in assignment \
                        and shape[dim] % n == 0:
                    assignment[dim] = mesh_axis
                    return

    if "model" in sizes:
        assign("model", MODEL_PREFS)
    if "data" in sizes:
        assign("data", DATA_PREFS)
    return P(*[assignment.get(d) for d in range(len(shape))])


def param_shardings(abstract_params: Any, axes: Any, mesh: Mesh) -> Any:
    """NamedSharding tree matching the params tree."""
    flat_p, treedef = jax.tree.flatten(abstract_params)
    flat_a = jax.tree.flatten(axes, is_leaf=lambda v: isinstance(v, tuple))[0]
    if len(flat_p) != len(flat_a):
        raise ValueError(f"params/axes mismatch: {len(flat_p)} vs {len(flat_a)}")
    out = []
    for leaf, ax in zip(flat_p, flat_a):
        ax = tuple(ax) + (None,) * (len(leaf.shape) - len(ax)) \
            if ax is not None else (None,) * len(leaf.shape)
        out.append(NamedSharding(mesh, spec_for(leaf.shape, ax, mesh)))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ #
# activations / batches / caches
# ------------------------------------------------------------------ #
def batch_spec(shape: tuple[int, ...], mesh: Mesh, *,
               seq_axis: int | None = 1) -> P:
    """Shard batch dim over (pod, data); fall back to sequence sharding over
    data when the batch is too small (long-context cells)."""
    sizes = _mesh_axis_sizes(mesh)
    pod = sizes.get("pod", 1)
    data = sizes.get("data", 1)
    b = shape[0]
    spec: list = [None] * len(shape)
    if b % (pod * data) == 0 and pod * data > 1:
        spec[0] = ("pod", "data") if pod > 1 else "data"
    elif b % data == 0 and data > 1:
        spec[0] = "data"
        if pod > 1 and seq_axis is not None and len(shape) > seq_axis \
                and shape[seq_axis] % pod == 0 and shape[seq_axis] > 1:
            spec[seq_axis] = "pod"
    elif seq_axis is not None and len(shape) > seq_axis and shape[seq_axis] > 1:
        ax = []
        if data > 1 and shape[seq_axis] % (pod * data) == 0 and pod > 1:
            ax = ["pod", "data"]
        elif data > 1 and shape[seq_axis] % data == 0:
            ax = ["data"]
        if ax:
            spec[seq_axis] = tuple(ax) if len(ax) > 1 else ax[0]
    return P(*spec)


def cache_shardings(cache: Any, mesh: Mesh, *, n_kv_heads: int,
                    batch: int) -> Any:
    """Heuristic decode-cache sharding: batch -> (pod,data) when divisible,
    long sequence dims -> data, kv-head-like dims -> model."""
    sizes = _mesh_axis_sizes(mesh)
    data = sizes.get("data", 1)
    model = sizes.get("model", 1)
    pod = sizes.get("pod", 1)

    def one(leaf):
        shape = leaf.shape
        spec: list = [None] * len(shape)
        used_model = False
        used_data = False
        # batch dim is 0 for unstacked, 1 for group-stacked caches
        bdim = 0 if (len(shape) > 0 and shape[0] == batch) else \
            (1 if len(shape) > 1 and shape[1] == batch else None)
        if bdim is not None and batch % (pod * data) == 0 and pod * data > 1:
            spec[bdim] = ("pod", "data") if pod > 1 else "data"
            used_data = True
        # model axis priority must mirror the decode compute policy
        # (attention._constrain_qkv): kv-head dim when divisible, else the
        # long sequence dim — never head_dim (a head_dim-sharded cache
        # forces a full-cache reshard against seq/head-sharded compute).
        if model > 1:
            hd = len(shape) - 2                            # the kv-head dim
            if hd >= 0 and hd != bdim and 1 < shape[hd] < 4096 \
                    and shape[hd] % model == 0:
                spec[hd] = "model"
                used_model = True
            if not used_model:
                for d in range(len(shape)):                # seq-like dims
                    if d != bdim and spec[d] is None and shape[d] >= 4096 \
                            and shape[d] % model == 0:
                        spec[d] = "model"
                        used_model = True
                        break
        for d in range(len(shape)):
            if spec[d] is not None or d == bdim:
                continue
            if not used_data and shape[d] >= 4096 \
                    and shape[d] % (pod * data) == 0:
                spec[d] = ("pod", "data") if pod > 1 else "data"
                used_data = True
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
