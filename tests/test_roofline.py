"""Roofline extraction: collective-bytes HLO parsing, per-device
cost_analysis semantics, and the loop-corrected probe algebra."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline import HW, CellReport, collective_bytes
from repro.roofline.probe import Terms


# ------------------------------------------------------------------ #
# HLO collective parser
# ------------------------------------------------------------------ #
HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[512,256]{1,0} all-gather(%p0), dimensions={0}
  %ar = bf16[1024]{0} all-reduce(%x), to_apply=%sum
  %rs = f32[64,256]{1,0} reduce-scatter(%ag), dimensions={0}
  %a2a = f32[128,256]{1,0} all-to-all(%p0), dimensions={0}
  %cps = (f32[32,32]{1,0}, f32[32,32]{1,0}) collective-permute-start(%y)
  %cpd = f32[32,32]{1,0} collective-permute-done(%cps)
  %ags = f32[256,16]{1,0} all-gather-start(%z), dimensions={0}
  %agd = f32[256,16]{1,0} all-gather-done(%ags)
  ROOT %t = f32[] constant(0)
}
"""


def test_collective_bytes_parser():
    out = collective_bytes(HLO_SAMPLE)
    assert out["all-gather"] == 512 * 256 * 4 + 256 * 16 * 4   # start counted once
    assert out["all-reduce"] == 1024 * 2                       # bf16
    assert out["reduce-scatter"] == 64 * 256 * 4
    assert out["all-to-all"] == 128 * 256 * 4
    assert out["collective-permute"] == 32 * 32 * 4            # tuple halved
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_collective_bytes_real_lowering():
    """An explicitly sharded psum must show up as all-reduce bytes."""
    mesh = jax.make_mesh((1,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P

    @jax.jit
    def f(a):
        return jax.lax.with_sharding_constraint(
            a.sum(axis=0, keepdims=True), NamedSharding(mesh, P()))

    # single device: no collectives expected — parser returns 0, not junk
    txt = f.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile().as_text()
    assert collective_bytes(txt)["total"] >= 0.0


# ------------------------------------------------------------------ #
# cost_analysis semantics the probe relies on
# ------------------------------------------------------------------ #
def _flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0))


def test_cost_analysis_counts_scan_body_once():
    """The documented XLA behaviour that motivates the probe corrections."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def body(c, w):
        return jnp.tanh(c @ w), None

    def stepL(L):
        ws = jax.ShapeDtypeStruct((L, 128, 128), jnp.float32)
        return _flops(lambda x, ws: jax.lax.scan(body, x, ws)[0], x, ws)

    f4, f16 = stepL(4), stepL(16)
    assert f4 == pytest.approx(f16, rel=0.01)        # body counted once
    one = 2 * 128 ** 3
    assert f4 == pytest.approx(one, rel=0.05)


def test_probe_correction_matches_unrolled():
    """step + (G-1)*group  ==  fully unrolled flops (the probe algebra)."""
    G = 8
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((G, 128, 128), jnp.float32)
    w1 = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def body(c, w):
        return jnp.tanh(c @ w), None

    f_step = _flops(lambda x, ws: jax.lax.scan(body, x, ws)[0], x, ws)
    f_grp = _flops(lambda x, w: body(x, w)[0], x, w1)
    f_unrl = _flops(
        lambda x, ws: jax.lax.scan(body, x, ws, unroll=G)[0], x, ws)
    corrected = f_step + (G - 1) * f_grp
    assert corrected == pytest.approx(f_unrl, rel=0.02)


def test_terms_algebra():
    a = Terms(1.0, 2.0, 3.0, {"all-reduce": 3.0})
    b = Terms(10.0, 20.0, 30.0, {"all-gather": 30.0})
    s = a + 2 * b
    assert s.flops == 21.0 and s.hbm == 42.0 and s.coll == 63.0
    assert s.coll_by_op == {"all-reduce": 3.0, "all-gather": 60.0}


def test_cell_report_bound_and_mfu():
    r = CellReport(
        arch="a", shape="s", mesh="m", chips=2,
        flops_per_chip=HW["peak_flops_bf16"] * 1e-3,     # 1 ms compute
        hbm_bytes_per_chip=HW["hbm_bw"] * 2e-3,          # 2 ms memory
        coll_bytes_per_chip=HW["ici_bw"] * 0.5e-3,       # 0.5 ms collective
        coll_by_op={}, peak_memory_per_chip=0.0,
        model_flops=HW["peak_flops_bf16"] * 1e-3 * 2 * 0.5,
        t_compute=1e-3, t_memory=2e-3, t_collective=0.5e-3)
    assert r.bound == "memory"
    assert r.t_total_overlap == pytest.approx(2e-3)
    assert r.mfu == pytest.approx(0.25)
    assert r.useful_flops_ratio == pytest.approx(0.5)
