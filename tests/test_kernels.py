"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle, swept
over sampled configs from each kernel's own search space + shape variants.

interpret mode executes the kernel body on CPU — the same BlockSpec/grid
program that runs on TPU — so this validates indexing, accumulation and
masking logic for every tunable parameter combination sampled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.space import AttentionProblem
from repro.kernels.common import rel_l2
from repro.kernels.conv2d.space import Conv2dProblem
from repro.kernels.dedisp.space import DedispProblem
from repro.kernels.expdist.space import ExpdistProblem
from repro.kernels.hotspot.space import HotspotProblem
from repro.kernels.matmul.space import GemmProblem
from repro.kernels.mla.space import MLADecodeProblem
from repro.kernels.nbody.space import NbodyProblem
from repro.kernels.pnpoly.space import PnpolyProblem

PROBLEMS = {
    "gemm": GemmProblem,
    "conv2d": Conv2dProblem,
    "nbody": NbodyProblem,
    "hotspot": HotspotProblem,
    "pnpoly": PnpolyProblem,
    "expdist": ExpdistProblem,
    "dedisp": DedispProblem,
    "attention": AttentionProblem,
    "mla_decode": MLADecodeProblem,
}

N_CONFIGS = 4          # sampled tunable configs per kernel


def _check(name, prob, config, key):
    inputs = prob.make_inputs(key, small=True)
    want = prob.run_reference(config, inputs)
    got = prob.run_kernel(config, inputs, interpret=True)
    err = rel_l2(got, want)
    assert err <= prob.tolerance(config) + 1e-12, \
        f"{name} {config}: rel_l2={err:.4g}"


@pytest.mark.parametrize("name", list(PROBLEMS))
def test_kernel_matches_oracle_across_configs(name):
    prob = PROBLEMS[name]()
    cfgs = prob.space.sample_distinct(N_CONFIGS, seed=42)
    # always include the deployment default where it is valid
    for i, cfg in enumerate(cfgs):
        _check(name, prob, cfg, jax.random.key(100 + i))


@pytest.mark.parametrize("name", ["gemm", "attention", "conv2d"])
def test_kernel_dtype_sweep(name):
    """Shape/dtype sweep for the LM-stack kernels (deliverable c)."""
    prob = PROBLEMS[name]()
    cfg = prob.space.sample_distinct(1, seed=7)[0]
    for i, dtype in enumerate((jnp.float32, jnp.bfloat16)):
        prob.dtype = dtype
        _check(name, prob, cfg, jax.random.key(i))


def test_gemm_shape_sweep():
    prob = GemmProblem()
    cfg = {"block_m": 64, "block_n": 128, "block_k": 128, "unroll_k": 1,
           "grid_order": "mn", "split_k": 1, "acc_dtype": "f32",
           "rhs_layout": "kn"}
    for m, n, k in ((128, 128, 128), (256, 128, 512), (128, 256, 256)):
        a = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
        b = jax.random.normal(jax.random.key(1), (k, n), jnp.bfloat16)
        c = jax.random.normal(jax.random.key(2), (m, n), jnp.bfloat16)
        from repro.kernels.matmul.kernel import gemm
        from repro.kernels.matmul.ref import gemm_reference
        got = gemm(a, b, c, alpha=1.0, beta=1.0, interpret=True, **cfg)
        want = gemm_reference(a, b, c, 1.0, 1.0)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("hq,hkv,block_h", [(4, 2, 1), (14, 2, 7),
                                             (12, 2, 6)])
def test_attention_causal_and_full(hq, hkv, block_h):
    """Groups of 7 and 6 fold their heads into the rows of one tile; the
    causal mask must follow each row's place within its own head (tq 128
    < tk 256 puts the diagonal at an offset)."""
    cfg = {"block_q": 64, "block_kv": 128, "block_h": block_h}
    from repro.kernels.attention.kernel import flash_attention
    from repro.kernels.attention.ref import mha_reference
    q = jax.random.normal(jax.random.key(0), (hq, 128, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (hkv, 256, 64), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (hkv, 256, 64), jnp.float32)
    for causal in (False, True):
        got = flash_attention(q, k, v, causal=causal, interpret=True, **cfg)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)


def test_ops_dispatch_uses_reference_on_cpu():
    """The ops wrapper runs the Pallas kernel with its default config on
    every backend: interpreted on the CPU only when asked, and never the
    reference in its place."""
    from repro.kernels.matmul.ops import gemm as gemm_op
    from repro.kernels.matmul.ref import gemm_reference
    a = jax.random.normal(jax.random.key(0), (512, 512), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (512, 256), jnp.float32)
    c = jax.random.normal(jax.random.key(2), (512, 256), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(gemm_op(a, b, c, interpret=True)),
        np.asarray(gemm_reference(a, b, c, 1.0, 1.0)), rtol=1e-4, atol=1e-3)
    with pytest.raises(ValueError, match="interpret"):
        gemm_op(a, b, c)            # compiled Pallas: refused by XLA:CPU


def test_invalid_configs_evaluate_to_inf():
    """Constraint-violating configs are invalid trials (the suite's analogue
    of a CUDA compile failure), never exceptions."""
    import math
    prob = GemmProblem()
    cfg = dict(prob.space.sample_distinct(1, seed=0)[0])
    cfg["block_m"] = 512
    cfg["block_k"] = 1024
    cfg["acc_dtype"] = "f32"
    cfg["block_n"] = 512
    t = prob.evaluate(cfg)          # VMEM constraint must trip
    if not prob.space.satisfies(cfg):
        assert not t.valid and math.isinf(t.objective)


# ------------------------------------------------------------------ #
# index-native evaluation: columnar features == scalar features
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def _problems():
    return {name: cls() for name, cls in PROBLEMS.items()}


@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_feature_columns_bitwise_equal_scalar(name, _problems):
    """Every kernel's vectorized ``feature_columns`` must reproduce the
    per-config ``features`` path bit for bit — columns, and therefore
    cost-model objectives, on every architecture."""
    from repro.core.costmodel import (ARCH_NAMES, FeatureBatch,
                                      estimate_seconds_batch)
    prob = _problems[name]
    comp = prob.space.compiled()
    assert comp is not None
    rows = comp.sample_rows_distinct(200, __import__("random").Random(3))
    cols = comp.value_columns(rows)
    cfgs = comp.decode_many(rows)
    for arch in ARCH_NAMES:
        fb = prob.feature_columns(cols, arch)
        assert fb is not None
        ref = FeatureBatch.from_features(
            [prob.features(c, arch) for c in cfgs])
        for field in FeatureBatch.FIELDS:
            got = np.broadcast_to(np.asarray(getattr(fb, field)), (len(rows),))
            assert np.array_equal(got, getattr(ref, field)), (arch, field)
        assert np.array_equal(
            np.broadcast_to(np.asarray(estimate_seconds_batch(fb, arch)),
                            (len(rows),)),
            estimate_seconds_batch(ref, arch)), arch


@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_vec_constraints_match_predicates(name, _problems):
    """All suite constraints carry vectorized forms that agree with their
    Python predicates over the whole cross product (the compiled mask is
    exactly the predicate-chain acceptance set)."""
    from repro.core.spacetable import CompiledSpace
    sp = _problems[name].space
    assert all(c.vec is not None for c in sp.constraints), name
    comp = sp.compiled()
    codes = CompiledSpace.codes_for(sp)
    names = sp.param_names
    pyvals = [p.values for p in sp.params]
    # spot-check a deterministic slice of rows (full sweep is the
    # spacetable property tests' job on random spaces)
    rows = np.unique(np.linspace(0, sp.cardinality - 1, 500, dtype=np.int64))
    for r in rows:
        cfg = {nm: pv[j] for nm, pv, j in zip(names, pyvals, codes[r])}
        assert bool(comp.mask[r]) == all(c.fn(cfg) for c in sp.constraints)


@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_rows_endpoints_match_evaluate_many(name, _problems):
    """``trials_for_rows`` / ``objectives_for_rows`` /
    ``objectives_for_rows_archs`` agree exactly with ``evaluate_many`` —
    including the small-batch scalar fallback (below the columnar
    threshold) and the shared-columns multi-arch sweep."""
    import random as _random

    from repro.core.costmodel import ARCH_NAMES
    prob = _problems[name]
    comp = prob.space.compiled()
    for n in (1, 3, 64):            # below and above the columnar threshold
        rows = comp.sample_rows_distinct(n, _random.Random(n))
        cfgs = comp.decode_many(rows)
        for arch in ("v4", "v6e"):
            want = [t.objective for t in prob.evaluate_many(cfgs, arch)]
            got_t = prob.trials_for_rows(rows, arch)
            assert [t.objective for t in got_t] == want
            assert [t.config for t in got_t] == cfgs
            assert prob.objectives_for_rows(rows, arch).tolist() == want
        multi = prob.objectives_for_rows_archs(rows, ARCH_NAMES)
        for i, arch in enumerate(ARCH_NAMES):
            assert multi[i].tolist() == \
                [t.objective for t in prob.evaluate_many(cfgs, arch)]
