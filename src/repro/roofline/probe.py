"""Loop-corrected roofline terms ("probe" lowerings).

``compiled.cost_analysis()`` counts the body of every ``while`` loop ONCE,
regardless of trip count (verified in tests/test_roofline.py).  Our models
scan over layer groups (and the recurrence blocks scan over time chunks /
tokens), so the raw step artifact under-reports FLOPs/bytes/collectives by
the product of trip counts — a >100x error for deep models.

Fix: compositional correction.  Lower (under the SAME mesh and shardings)

  * T_step   — the full step with ``microbatches=1`` (group scan counted once),
  * T_group  — ONE pattern-group body, standalone (train: vjp w/ remat, so
               fwd + recompute + bwd are counted, matching one iteration of
               the fwd+bwd scan pair),
  * T_enc    — one encoder layer body (whisper only),

and assemble

  T_true = T_step + (G - 1) * T_group + (E - 1) * T_enc + recurrence_extra

where G = number of scanned layer groups, E = encoder layers.  Every term is
still sourced from compiled artifacts (cost_analysis + optimized-HLO
collective parsing); only the *combination* is ours.

``recurrence_extra`` covers the token-level scans inside RWKV6 / RG-LRU
blocks (a scan inside a scan inside a scan): their bodies are tiny
elementwise state updates with zero collectives, so the missing
``G * (T - 1)`` executions are added analytically (closed-form FLOPs/bytes,
divided by the data-parallel extent — the state is batch-sharded and
replicated over ``model``).

Microbatching note: the deploy step uses gradient accumulation
(``microbatches=k``); the probe uses k=1 (identical FLOPs; bytes/collective
deltas from re-reading / re-gathering weights per microbatch are reported as
an analytic ``mb_extra`` column, not folded into the headline terms).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.common import SHAPES
from ..distributed import sharding as shd
from ..models import transformer as tfm
from ..models.model import Model, build_model
from ..models.rwkv6 import HEAD_DIM as RWKV_HEAD_DIM
from .roofline import HW, CellReport, collective_bytes


@dataclasses.dataclass
class Terms:
    """Per-chip (flops, hbm bytes, collective bytes) of one artifact."""
    flops: float = 0.0
    hbm: float = 0.0
    coll: float = 0.0
    coll_by_op: dict = dataclasses.field(default_factory=dict)

    def __add__(self, o: "Terms") -> "Terms":
        ops = dict(self.coll_by_op)
        for k, v in o.coll_by_op.items():
            ops[k] = ops.get(k, 0.0) + v
        return Terms(self.flops + o.flops, self.hbm + o.hbm,
                     self.coll + o.coll, ops)

    def __mul__(self, c: float) -> "Terms":
        return Terms(self.flops * c, self.hbm * c, self.coll * c,
                     {k: v * c for k, v in self.coll_by_op.items()})

    __rmul__ = __mul__


def measure(lowered) -> Terms:
    """Compile a lowered artifact and extract per-chip terms."""
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    coll = collective_bytes(compiled.as_text())
    return Terms(float(ca.get("flops", 0.0)),
                 float(ca.get("bytes accessed", 0.0)),
                 coll["total"],
                 {k: v for k, v in coll.items() if k != "total"})


# ------------------------------------------------------------------ #
# sharding helpers
# ------------------------------------------------------------------ #
def _unstack(s: NamedSharding, mesh: Mesh) -> NamedSharding:
    """Drop the leading (layers) axis of a stacked-parameter sharding."""
    spec = tuple(s.spec)
    return NamedSharding(mesh, P(*spec[1:]) if spec else P())


def _unstack_tree(tree, mesh):
    return jax.tree.map(lambda s: _unstack(s, mesh), tree,
                        is_leaf=lambda v: isinstance(v, NamedSharding))


def _slice0_abs(tree):
    """ShapeDtypeStruct tree with the leading axis removed."""
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), tree)


# ------------------------------------------------------------------ #
# group-body probes
# ------------------------------------------------------------------ #
def _group_fwd_fn(model: Model, *, causal=True, with_enc=False):
    cfg, pattern = model.cfg, model.pattern

    def group_fwd(gp, x, enc_out=None):
        positions = jnp.arange(x.shape[1])[None]
        aux = jnp.zeros((), jnp.float32)
        h = x
        for i, spec in enumerate(pattern):
            h, _, a = tfm._block_forward(
                gp[i], h, cfg, spec, positions=positions,
                enc_out=enc_out, causal=causal, make_cache=False)
            aux = aux + a
        return h, aux

    if with_enc:
        return group_fwd
    return lambda gp, x: group_fwd(gp, x, None)


def probe_group_train(model: Model, b: int, t: int, mesh: Mesh,
                      gp_abs, gp_shard, enc_len: int | None = None):
    """One group's fwd + (remat) recompute + bwd — one iteration of the
    fwd/bwd scan pair."""
    cfg = model.cfg
    with_enc = enc_len is not None
    f = _group_fwd_fn(model, with_enc=with_enc)
    if cfg.remat:
        f = jax.checkpoint(f)

    if with_enc:
        def probe(gp, x, enc_out, ct):
            (h, aux), vjp = jax.vjp(f, gp, x, enc_out)
            return h, vjp((ct, jnp.ones((), jnp.float32)))
    else:
        def probe(gp, x, ct):
            (h, aux), vjp = jax.vjp(f, gp, x)
            return h, vjp((ct, jnp.ones((), jnp.float32)))

    x_abs = jax.ShapeDtypeStruct((b, t, cfg.d_model), jnp.bfloat16)
    x_sh = NamedSharding(mesh, shd.batch_spec(x_abs.shape, mesh))
    args = [gp_abs, x_abs]
    shardings = [gp_shard, x_sh]
    if with_enc:
        e_abs = jax.ShapeDtypeStruct((b, enc_len, cfg.d_model), jnp.bfloat16)
        args.append(e_abs)
        shardings.append(NamedSharding(mesh, shd.batch_spec(e_abs.shape, mesh)))
    args.append(x_abs)          # cotangent, same shape/sharding as x
    shardings.append(x_sh)
    with shd.use_mesh(mesh):
        return jax.jit(probe, in_shardings=tuple(shardings)).lower(*args)


def probe_group_fwd(model: Model, b: int, t: int, mesh: Mesh,
                    gp_abs, gp_shard, enc_len: int | None = None):
    cfg = model.cfg
    with_enc = enc_len is not None
    f = _group_fwd_fn(model, with_enc=with_enc)
    x_abs = jax.ShapeDtypeStruct((b, t, cfg.d_model), jnp.bfloat16)
    x_sh = NamedSharding(mesh, shd.batch_spec(x_abs.shape, mesh))
    args, shardings = [gp_abs, x_abs], [gp_shard, x_sh]
    if with_enc:
        e_abs = jax.ShapeDtypeStruct((b, enc_len, cfg.d_model), jnp.bfloat16)
        args.append(e_abs)
        shardings.append(NamedSharding(mesh, shd.batch_spec(e_abs.shape, mesh)))
    with shd.use_mesh(mesh):
        return jax.jit(f, in_shardings=tuple(shardings)).lower(*args)


def probe_group_decode(model: Model, b: int, mesh: Mesh, gp_abs, gp_shard,
                       cache_abs, cache_shard, enc_len: int | None = None):
    cfg, pattern = model.cfg, model.pattern
    with_enc = enc_len is not None

    def probe(gp, caches, x, position, enc_out=None):
        new = []
        h = x
        for i, spec in enumerate(pattern):
            h, c = tfm._block_decode(gp[i], h, caches[i], cfg, spec,
                                     position=position, enc_out=enc_out)
            new.append(c)
        return h, new

    x_abs = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)
    x_sh = NamedSharding(mesh, shd.batch_spec(x_abs.shape, mesh,
                                              seq_axis=None))
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    args = [gp_abs, cache_abs, x_abs, pos_abs]
    shardings = [gp_shard, cache_shard, x_sh, NamedSharding(mesh, P())]
    if with_enc:
        e_abs = jax.ShapeDtypeStruct((b, enc_len, cfg.d_model), jnp.bfloat16)
        args.append(e_abs)
        shardings.append(NamedSharding(mesh, shd.batch_spec(e_abs.shape, mesh)))
        fn = probe
    else:
        fn = lambda gp, caches, x, position: probe(gp, caches, x, position)
    with shd.use_mesh(mesh):
        return jax.jit(fn, in_shardings=tuple(shardings)).lower(*args)


def probe_encoder_layer(model: Model, b: int, t: int, mesh: Mesh,
                        lp_abs, lp_shard, train: bool):
    """One whisper encoder layer (fwd, or fwd+bwd when training)."""
    cfg = model.cfg
    enc_spec = tfm.BlockSpec(kind="attn", mlp="gelu")

    def fwd(lp, x):
        positions = jnp.arange(x.shape[1])[None]
        h, _, _ = tfm._block_forward(lp, x, cfg, enc_spec,
                                     positions=positions, causal=False,
                                     make_cache=False)
        return h

    f = jax.checkpoint(fwd) if (train and cfg.remat) else fwd
    x_abs = jax.ShapeDtypeStruct((b, t, cfg.d_model), jnp.bfloat16)
    x_sh = NamedSharding(mesh, shd.batch_spec(x_abs.shape, mesh))
    if train:
        def probe(lp, x, ct):
            h, vjp = jax.vjp(f, lp, x)
            return h, vjp(ct)
        args = (lp_abs, x_abs, x_abs)
        shardings = (lp_shard, x_sh, x_sh)
    else:
        probe, args, shardings = f, (lp_abs, x_abs), (lp_shard, x_sh)
    with shd.use_mesh(mesh):
        return jax.jit(probe, in_shardings=shardings).lower(*args)


# ------------------------------------------------------------------ #
# analytic recurrence extras (token-level scans)
# ------------------------------------------------------------------ #
def recurrence_extra(cfg, kind: str, b: int, t: int, n_layers_of_kind: int,
                     mesh: Mesh, train: bool) -> Terms:
    """FLOPs/bytes of the ``n_layers * (T - 1)`` token-scan-body executions
    the lowered artifacts do not count.  Zero collectives (state updates are
    elementwise, batch-sharded).  Train multiplies by 4: fwd + chunk-remat
    recompute + ~2x backward."""
    if t <= 1:
        return Terms()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("pod", 1) * sizes.get("data", 1)
    shard = dp if b % dp == 0 else 1      # state replicated over `model`
    if kind == "rwkv6":
        h = cfg.d_model // RWKV_HEAD_DIM
        state = h * RWKV_HEAD_DIM * RWKV_HEAD_DIM          # per batch elem
        flops_tok = 7.0 * state * b                         # outer+dot+decay
        bytes_tok = (2 * 4 * state + 4 * 4 * h * RWKV_HEAD_DIM) * b
    elif kind == "rglru":
        w = cfg.rglru_width or cfg.d_model
        flops_tok = 3.0 * w * b
        bytes_tok = 4.0 * 4 * w * b
    else:
        return Terms()
    mult = 4.0 if train else 1.0
    n_exec = n_layers_of_kind * (t - 1)
    return Terms(flops_tok * n_exec * mult / shard,
                 bytes_tok * n_exec * mult / shard, 0.0)


def _sdpa_policy_shardings(b, t, h, hkv, mesh):
    """Input shardings matching attention._constrain_qkv's opt policy."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    batch = shd.batch_spec((b,), mesh)[0]
    P_ = __import__("jax").sharding.PartitionSpec
    if tp > 1 and h % tp == 0 and hkv % tp == 0:
        q = P_(batch, None, "model", None)
        kv = P_(batch, None, "model", None)
    elif tp > 1 and t % tp == 0 and t > 1:
        q = P_(batch, "model", None, None)
        kv = P_(batch, None, None, None)
    else:
        q = kv = P_(batch, None, None, None)
    return (NamedSharding(mesh, q), NamedSharding(mesh, kv))


def attention_substitution(cfg, b: int, t: int, mesh: Mesh, *, train: bool,
                           window: int | None, n_layers: int,
                           verbose: bool) -> Terms:
    """Per-layer delta: −(measured jnp softmax chain) + (Pallas flash kernel
    traffic from the suite's own AttentionProblem cost features).

    The XLA lowering materializes the (tq × tk) score tensor between fusions
    — on TPU that layer deploys as the tuned flash kernel
    (repro.kernels.attention), whose HBM traffic is q/k/v/o + running stats.
    Substituting the kernel's terms for the jnp chain's is how the framework
    composes graph-level and kernel-level rooflines.  Only applied under
    ``opt_attn`` (the baseline keeps the faithful jnp lowering)."""
    from ..kernels.attention.kernel import tiling
    from ..kernels.attention.ops import DEFAULT_CONFIG
    from ..kernels.attention.space import AttentionProblem
    from ..models import attention as attn_lib

    h, dh = cfg.n_heads, cfg.d_head
    hkv = cfg.n_kv_heads * cfg.kv_repeat
    chips = mesh.devices.size

    # --- measured: the exact jnp sub-expression the group body contains --- #
    def sdpa_fn(q, k, v):
        q2, k2, v2, mode = attn_lib._constrain_qkv(q, k, v, opt=True)
        if t >= 2048:
            out = attn_lib._sdpa_chunked(q2, k2, v2, window=window,
                                         causal=True)
        else:
            bias = attn_lib._mask_bias(t, t, 0, window, True)
            out = attn_lib._sdpa(q2, k2, v2, bias)
        if mode == "heads":
            out = shd.constrain(out, ("pod", "data"), None, "model", None)
        elif mode == "seq":
            out = shd.constrain(out, ("pod", "data"), "model", None, None)
        return out

    f = jax.checkpoint(sdpa_fn) if (train and cfg.remat) else sdpa_fn
    q_abs = jax.ShapeDtypeStruct((b, t, h, dh), jnp.bfloat16)
    kv_abs = jax.ShapeDtypeStruct((b, t, hkv, dh), jnp.bfloat16)
    q_sh, kv_sh = _sdpa_policy_shardings(b, t, h, hkv, mesh)
    with shd.use_mesh(mesh):
        if train:
            def probe(q, k, v, ct):
                y, vjp = jax.vjp(f, q, k, v)
                return y, vjp(ct)
            lowered = jax.jit(probe, in_shardings=(q_sh, kv_sh, kv_sh, q_sh)
                              ).lower(q_abs, kv_abs, kv_abs, q_abs)
        else:
            lowered = jax.jit(f, in_shardings=(q_sh, kv_sh, kv_sh)
                              ).lower(q_abs, kv_abs, kv_abs)
    t_jnp = measure(lowered)

    # --- substituted: tuned flash-kernel terms (suite cost features) ------ #
    prob = AttentionProblem(shape={"hq": b * h, "hkv": b * hkv,
                                   "tq": t, "tk": t, "d": dh})
    # the heads a program takes at this group, as the kernel resolves them
    (bh, _, _), _ = tiling((b * h, t, dh), (b * hkv, t, dh),
                           block_q=DEFAULT_CONFIG["block_q"],
                           block_kv=DEFAULT_CONFIG["block_kv"],
                           block_h=DEFAULT_CONFIG["block_h"])
    feats = prob.features({**DEFAULT_CONFIG, "block_h": bh}, "v5e")
    fl = feats.mxu_flops + feats.vpu_flops + feats.transcendental_ops
    hb = feats.hbm_bytes
    if window and window < t // 2:      # local layers do ~t*w work
        scale = (2.0 * window) / t
        fl *= scale
        hb *= scale
    if train:                           # fwd + remat refwd + bwd
        fl *= 3.5
        hb *= 3.0
    t_flash = Terms(fl / chips, hb / chips, 0.0)

    delta = n_layers * (t_flash + (-1.0) * t_jnp)
    if verbose:
        print(f"  [probe] sdpa swap x{n_layers}: jnp "
              f"{t_jnp.hbm / 1e9:.1f} GB -> flash {t_flash.hbm / 1e9:.2f} GB"
              f" per layer per chip", flush=True)
    return delta


def mb_extra(cfg, mesh: Mesh, microbatches: int) -> Terms:
    """Analytic deltas of running the deploy step with gradient accumulation
    (k microbatches) instead of the probed k=1: weights are re-read from HBM
    and re-gathered over the FSDP axis (k-1) extra times."""
    if microbatches <= 1:
        return Terms()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    chips = mesh.devices.size
    data = sizes.get("data", 1) * sizes.get("pod", 1)
    n = cfg.param_count()
    param_bytes_chip = 2.0 * n / chips                     # bf16 shard
    # per extra microbatch: fwd + bwd re-read weights (~2x), FSDP re-gather
    gather = param_bytes_chip * (data - 1)                 # bytes received
    k = microbatches - 1
    return Terms(0.0, k * 2.0 * param_bytes_chip, k * gather,
                 {"all-gather": k * gather})


# ------------------------------------------------------------------ #
# assembly
# ------------------------------------------------------------------ #
def corrected_cell_terms(cfg, shape_name: str, mesh: Mesh,
                         verbose: bool = True) -> dict:
    """Lower + compile the probe set for one (arch, shape) cell and return
    the loop-corrected per-chip terms plus per-artifact breakdown."""
    from ..launch.steps import lower_cell, plan_cell   # local: import cycle

    cell = SHAPES[shape_name]
    kind = cell["kind"]
    b, s = cell["global_batch"], cell["seq_len"]
    model = build_model(cfg)
    G = model.n_groups
    E = cfg.n_enc_layers

    # --- T_step: full step, microbatches=1 --------------------------- #
    plan = plan_cell(cfg, shape_name, mesh, microbatches=1)
    t_step = measure(lower_cell(plan, mesh))
    if verbose:
        print(f"  [probe] step: {t_step.flops/1e12:.3f} TF "
              f"{t_step.hbm/1e9:.2f} GB {t_step.coll/1e9:.3f} GBcoll",
              flush=True)

    breakdown = {"step": t_step}
    total = Terms() + t_step

    # decoder sequence length as seen by the blocks
    if cfg.frontend == "audio":
        t_dec = 448 if kind in ("train", "prefill") else 1
        enc_len = s if kind in ("train", "prefill") else 1500
    else:
        # vision: blocks see patches + text = the full s tokens
        t_dec = s if kind in ("train", "prefill") else 1
        enc_len = None

    # --- T_group ------------------------------------------------------ #
    if G > 0:
        abstract_params = plan.args[0]
        p_shard = plan.in_shardings[0]
        gp_abs = [_slice0_abs(t) for t in abstract_params["blocks"]]
        gp_shard = [_unstack_tree(t, mesh) for t in p_shard["blocks"]]
        if kind == "train":
            lowered = probe_group_train(model, b, t_dec, mesh, gp_abs,
                                        gp_shard, enc_len=enc_len)
        elif kind == "prefill":
            lowered = probe_group_fwd(model, b, t_dec, mesh, gp_abs,
                                      gp_shard, enc_len=enc_len)
        else:
            batch = plan.args[1]
            cache_abs = [_slice0_abs(t) for t in batch["cache"]["groups"]]
            cache_shard = [_unstack_tree(t, mesh) for t in
                           plan.in_shardings[1]["cache"]["groups"]]
            lowered = probe_group_decode(
                model, b, mesh, gp_abs, gp_shard, cache_abs, cache_shard,
                enc_len=enc_len)
        t_group = measure(lowered)
        breakdown["group"] = t_group
        total = total + (G - 1) * t_group
        if verbose:
            print(f"  [probe] group x{G}: {t_group.flops/1e12:.3f} TF "
                  f"{t_group.hbm/1e9:.2f} GB {t_group.coll/1e9:.3f} GBcoll",
                  flush=True)

    # --- T_enc (whisper) ---------------------------------------------- #
    if E > 0 and kind in ("train", "prefill"):
        abstract_params = plan.args[0]
        p_shard = plan.in_shardings[0]
        lp_abs = _slice0_abs(abstract_params["encoder"])
        lp_shard = _unstack_tree(p_shard["encoder"], mesh)
        lowered = probe_encoder_layer(model, b, s, mesh, lp_abs, lp_shard,
                                      train=(kind == "train"))
        t_enc = measure(lowered)
        breakdown["enc_layer"] = t_enc
        total = total + (E - 1) * t_enc
        if verbose:
            print(f"  [probe] enc x{E}: {t_enc.flops/1e12:.3f} TF", flush=True)

    # --- tuned-kernel substitution for the attention hot loop ----------- #
    if cfg.opt_attn and kind in ("train", "prefill") and t_dec >= 2048:
        windows = {}
        for i in range(cfg.n_layers):
            spec = cfg.pattern[i % len(cfg.pattern)]
            if spec.kind == "attn":
                windows[spec.window] = windows.get(spec.window, 0) + 1
        for w, n_l in windows.items():
            delta = attention_substitution(
                cfg, b, t_dec, mesh, train=(kind == "train"), window=w,
                n_layers=n_l, verbose=verbose)
            breakdown[f"sdpa_swap_w{w}"] = delta
            total = total + delta

    # --- recurrence token-scan extras ---------------------------------- #
    seq_for_scan = t_dec if kind in ("train", "prefill") else 1
    for scan_kind in ("rwkv6", "rglru"):
        n_of_kind = sum(1 for i in range(cfg.n_layers)
                        if cfg.pattern[i % len(cfg.pattern)].kind == scan_kind)
        if n_of_kind:
            extra = recurrence_extra(cfg, scan_kind, b, seq_for_scan,
                                     n_of_kind, mesh, train=(kind == "train"))
            breakdown[f"recurrence_{scan_kind}"] = extra
            total = total + extra

    # --- deploy-microbatching analytic extras -------------------------- #
    from ..launch.steps import microbatch_count
    mb = microbatch_count(cfg, shape_name, mesh)
    extra_mb = mb_extra(cfg, mesh, mb) if kind == "train" else Terms()
    breakdown["mb_extra"] = extra_mb

    return {"total": total, "breakdown": breakdown, "microbatches_deploy": mb}


def corrected_report(cfg, shape_name: str, mesh: Mesh, *, arch: str,
                     mesh_name: str, model_flops_value: float,
                     verbose: bool = True) -> tuple[CellReport, dict]:
    """CellReport built from loop-corrected terms (+ the probe breakdown)."""
    res = corrected_cell_terms(cfg, shape_name, mesh, verbose=verbose)
    t: Terms = res["total"]
    report = CellReport(
        arch=arch, shape=shape_name, mesh=mesh_name,
        chips=mesh.devices.size,
        flops_per_chip=t.flops, hbm_bytes_per_chip=t.hbm,
        coll_bytes_per_chip=t.coll, coll_by_op=t.coll_by_op,
        peak_memory_per_chip=0.0,        # deploy lowering owns memory fit
        model_flops=model_flops_value,
        t_compute=t.flops / HW["peak_flops_bf16"],
        t_memory=t.hbm / HW["hbm_bw"],
        t_collective=t.coll / HW["ici_bw"],
    )
    return report, res
