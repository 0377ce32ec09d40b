"""MLA decode kernel (DeepSeek-V2's absorbed latent attention) against its
plain reference and against the model's own attention, in Pallas interpret
mode at a small size: 3 sequences of 1, 300 and 512 cached tokens (300 is a
multiple of no ``block_kv``), 16 heads, latent width 128, rope width 64."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import rel_l2
from repro.kernels.mla import ops
from repro.kernels.mla.space import MLADecodeProblem, log_uniform_lengths
from repro.models.attention import NEG_INF, _sdpa

PROB = MLADecodeProblem()
CONFIGS = PROB.space.sample_distinct(6, seed=5) + [ops.DEFAULT_CONFIG]


def _inputs(seed=0):
    return PROB.make_inputs(jax.random.key(seed), small=True)


def _per_sequence_errors(got, want, lengths):
    return [rel_l2(np.asarray(got[b], np.float32), np.asarray(want[b]))
            for b in range(len(lengths))]


@pytest.mark.parametrize("config", CONFIGS,
                         ids=lambda c: "-".join(str(v) for v in c.values()))
def test_kernel_matches_reference(config):
    x = _inputs(seed=1)
    want = PROB.run_reference(config, x)
    got = PROB.run_kernel(config, x, interpret=True)
    assert got.shape == x["q_lat"].shape and got.dtype == jnp.bfloat16
    # each sequence on its own: the length-1 one is a copy of one cache row
    # and would hide the others' error in a norm over the batch
    for err in _per_sequence_errors(got, want, x["lengths"]):
        assert err <= PROB.tolerance(config), (config, err)


def test_cache_past_the_lengths_is_masked():
    """A planted fault: the cache past each length scaled by 1e3.  The
    kernel's answer does not move; without the lengths it is ruined."""
    x = _inputs(seed=2)
    t = x["ckv"].shape[1]
    dead = (jnp.arange(t)[None, :] >= x["lengths"][:, None])[..., None]
    poisoned = dict(x, ckv=jnp.where(dead, x["ckv"] * 1e3, x["ckv"]),
                    k_pe=jnp.where(dead, x["k_pe"] * 1e3, x["k_pe"]))
    cfg = {"block_kv": 256, "block_h": 16, "acc_dtype": "f32"}
    clean = PROB.run_kernel(cfg, x, interpret=True)
    got = PROB.run_kernel(cfg, poisoned, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    unmasked = PROB.run_kernel(
        cfg, dict(poisoned, lengths=jnp.full_like(x["lengths"], t)),
        interpret=True)
    want = PROB.run_reference(cfg, x)
    assert rel_l2(got, want) <= PROB.tolerance(cfg)
    assert rel_l2(unmasked, want) > 0.5


def test_absorbed_decode_equals_materialized_attention():
    """``o_lat . W_uv`` equals the model's attention (``_sdpa``) over the
    materialized heads ``k = [c_kv . W_uk, k_pe]``, ``v = c_kv . W_uv``,
    with the length mask as an additive bias.  ``_sdpa`` scales by
    ``(nope + rope)^-0.5``, which the kernel is given."""
    b, h, c, r, t, nope, dv = 3, 16, 128, 64, 512, 128, 128
    lengths = jnp.asarray([1, 300, 512], jnp.int32)
    ks = jax.random.split(jax.random.key(3), 6)
    f32 = jnp.float32
    q_nope = jax.random.normal(ks[0], (b, h, nope), f32)
    q_pe = jax.random.normal(ks[1], (b, h, r), f32).astype(jnp.bfloat16)
    ckv = jax.random.normal(ks[2], (b, t, c), f32).astype(jnp.bfloat16)
    k_pe = jax.random.normal(ks[3], (b, t, r), f32).astype(jnp.bfloat16)
    w_uk = jax.random.normal(ks[4], (c, h, nope), f32) * c ** -0.5
    w_uv = jax.random.normal(ks[5], (c, h, dv), f32) * c ** -0.5

    with jax.default_matmul_precision("highest"):
        q_lat = jnp.einsum("bhk,chk->bhc", q_nope, w_uk)
        # the kernel reads q_lat in bf16: its rounding is within tolerance
        q_lat = q_lat.astype(jnp.bfloat16)
        o_lat = ops.mla_decode(q_lat, q_pe, ckv, k_pe, lengths,
                               scale=(nope + r) ** -0.5,
                               config={"block_kv": 128, "block_h": 8},
                               interpret=True)
        got = jnp.einsum("bhc,chk->bhk", o_lat.astype(f32), w_uv)

        k_nope = jnp.einsum("btc,chk->bthk", ckv.astype(f32), w_uk)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe.astype(f32)[:, :, None, :],
                                      (b, t, h, r))], axis=-1)
        v = jnp.einsum("btc,chk->bthk", ckv.astype(f32), w_uv)
        q = jnp.concatenate([q_nope, q_pe.astype(f32)], axis=-1)[:, None]
        bias = jnp.where(jnp.arange(t)[None, :] < lengths[:, None], 0.0,
                         NEG_INF)[:, None, None, None, :]
        want = _sdpa(q, k, v, bias)[:, 0]                  # (b, h, dv)

    for err in _per_sequence_errors(got, want, lengths):
        assert err <= PROB.tolerance({}), err


def test_cost_model_counts_live_tiles_only():
    prob = MLADecodeProblem({"b": 3, "h": 16, "c": 128, "r": 64,
                             "t_max": 512, "lengths": (1, 300, 512)})
    cfg = {"block_kv": 256, "block_h": 8, "acc_dtype": "f32"}
    f = prob.features(cfg, "v5e")
    live = 1 + 2 + 2                   # tiles of 256 holding a live token
    assert f.mxu_flops == 2.0 * 16 * (2 * 128 + 64) * 256 * live
    # each live tile is streamed once per group of 8 heads
    assert f.hbm_bytes == 2 * live * 256 * (128 + 64) * 2 \
        + 3 * 16 * (2 * 128 + 64) * 2
    # every step of the grid, dead ones too
    assert f.grid_steps == 3 * 2 * 2


def test_default_shape_is_the_cells_draw():
    lengths = MLADecodeProblem.default_shape["lengths"]
    assert lengths == log_uniform_lengths(64, 4096, 65536)
    assert len(lengths) == 64 and sum(lengths) == 1_402_538
    assert 4096 <= min(lengths) and max(lengths) < 65536
