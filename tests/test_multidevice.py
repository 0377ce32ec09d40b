"""Multi-device integration tests.

jax locks the device count at first backend init, so every case here runs in
a fresh subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
— the same mechanism the 512-way dry-run uses, scaled to test size.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(body: str, devices: int = 8, timeout: int = 420):
    prog = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            "from jax.sharding import AxisType\n"
            + textwrap.dedent(body))
    env = dict(os.environ,
               PYTHONPATH=f"{REPO / 'src'}:{os.environ.get('PYTHONPATH', '')}")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """One real sharded train step on a 4x2 mesh == the single-device step
    (bitwise-tolerant): the SPMD partition must not change the math."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS, reduce_config
    from repro.data import DataConfig, make_pipeline
    from repro.distributed import sharding as shd
    from repro.launch.steps import make_train_step
    from repro.models import build_model
    from repro.train.optimizer import OptimizerConfig, init_opt_state

    cfg = reduce_config(ARCHS["qwen3-8b"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    opt_cfg = OptimizerConfig(lr=1e-3)
    opt = init_opt_state(opt_cfg, params)
    batch = make_pipeline(DataConfig(vocab=cfg.vocab, seq_len=32,
                                     global_batch=8)).batch_at(0)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    step = make_train_step(model, opt_cfg, microbatches=1)

    # single device reference
    p1, o1, m1 = jax.jit(step)(params, opt, batch)

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    if model.axes is None:
        jax.eval_shape(model.init, jax.random.key(0))
    p_sh = shd.param_shardings(jax.eval_shape(lambda: params), model.axes,
                               mesh)
    with shd.use_mesh(mesh):
        params_s = jax.device_put(params, p_sh)
        opt_s = init_opt_state(opt_cfg, params_s)
        b_sh = {k: jax.NamedSharding(mesh, shd.batch_spec(v.shape, mesh))
                for k, v in batch.items()}
        batch_s = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
        p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)

    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2, \
        (float(m1["loss"]), float(m2["loss"]))
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
    worst = max(jax.tree.leaves(d))
    assert worst < 5e-2, worst
    print("OK sharded==single loss", float(m1["loss"]))
    """)


def test_gpipe_pipeline_matches_serial():
    """pipeline_apply over a 4-stage mesh == applying the 4 stage fns
    serially; also checks grad flows through ppermute."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import bubble_fraction, pipeline_apply

    mesh = jax.make_mesh((4, 2), ("stage", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    S, NM, MB, D = 4, 8, 4, 16
    ks = jax.random.split(jax.random.key(0), S)
    Ws = jnp.stack([jax.random.normal(k, (D, D)) * 0.3 for k in ks])

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    apply = pipeline_apply(stage_fn, mesh, axis="stage")
    x = jax.random.normal(jax.random.key(1), (NM, MB, D))
    got = jax.jit(apply)(Ws, x)

    want = x
    for s in range(S):
        want = stage_fn(Ws[s], want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # differentiability (backward pipeline via ppermute transpose)
    def loss(Ws):
        return jnp.sum(apply(Ws, x) ** 2)
    g = jax.grad(loss)(Ws)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).max()) > 0
    assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
    print("OK pipeline")
    """)


def test_dryrun_cell_on_8_devices():
    """The dry-run driver machinery end-to-end on a small mesh: lower,
    compile, cost-analyse a reduced arch (proves plan_cell/lower_cell are
    mesh-size agnostic)."""
    _run("""
    import jax
    from repro.configs import ARCHS, reduce_config
    from repro.launch.steps import input_specs, lower_cell, plan_cell
    from repro.roofline import analyze_compiled

    import dataclasses
    cfg = reduce_config(ARCHS["granite-moe-3b-a800m"])
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    plan = plan_cell(cfg, "train_4k", mesh, microbatches=1)
    lowered = lower_cell(plan, mesh)
    compiled = lowered.compile()
    rep = analyze_compiled(compiled, chips=8, arch="granite-red",
                           shape="train_4k", mesh="4x2",
                           model_flops_value=1.0)
    assert rep.flops_per_chip > 0
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    print("OK dryrun-small", rep.bound)
    """, timeout=600)


def test_elastic_checkpoint_across_mesh_shapes():
    """Save params sharded on a 4x2 mesh, restore onto 2x4 — the elastic
    restart story with real (multi-)device placement."""
    _run("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS, reduce_config
    from repro.distributed import sharding as shd
    from repro.models import build_model
    from repro.train import checkpoint as ckpt

    cfg = reduce_config(ARCHS["qwen3-14b"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    jax.eval_shape(model.init, jax.random.key(0))

    mesh1 = jax.make_mesh((4, 2), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    sh1 = shd.param_shardings(jax.eval_shape(lambda: params), model.axes,
                              mesh1)
    p1 = jax.device_put(params, sh1)
    d = tempfile.mkdtemp()
    ckpt.save(d, 3, p1)

    mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    sh2 = shd.param_shardings(jax.eval_shape(lambda: params), model.axes,
                              mesh2)
    like = jax.eval_shape(lambda: params)
    p2, extra = ckpt.restore(d, like, shardings=sh2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK elastic")
    """)
