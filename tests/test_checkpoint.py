"""Checkpoint fault-tolerance contract: atomic commit, integrity, retention,
auto-resume, and structure checks."""

import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt


def _tree(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (8, 16), jnp.float32),
            "b": jnp.arange(16, dtype=jnp.bfloat16),
            "nested": {"m": jnp.full((4,), 3, jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 7, t, extra={"step": 7, "note": "x"})
    like = jax.eval_shape(lambda: t)
    got, extra = ckpt.restore(tmp_path, like)
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_latest_and_retention(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, t, keep=2)
    assert ckpt.latest_step(tmp_path) == 5
    kept = sorted(p.name for p in Path(tmp_path).glob("step_*"))
    assert len(kept) == 2 and kept[-1].endswith("000000005")


def test_crash_mid_save_leaves_previous_intact(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 1, t)
    # simulate a crash: stale tmp dir from a dead writer
    tmp_dir = Path(tmp_path) / "step_000000002.tmp"
    tmp_dir.mkdir()
    (tmp_dir / "junk").write_bytes(b"partial")
    assert ckpt.latest_step(tmp_path) == 1
    got, _ = ckpt.restore(tmp_path, jax.eval_shape(lambda: t))
    assert got is not None
    ckpt.save(tmp_path, 3, t)                    # sweeps the tmp litter
    assert not tmp_dir.exists()


def test_corrupt_shard_fails_loudly(tmp_path):
    t = _tree()
    d = ckpt.save(tmp_path, 1, t)
    shard = d / "shard_00000.bin.zst"
    raw = bytearray(shard.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    shard.write_bytes(bytes(raw))
    with pytest.raises((IOError, zlib.error, Exception)):
        ckpt.restore(tmp_path, jax.eval_shape(lambda: t))


def test_structure_mismatch_rejected(tmp_path):
    ckpt.save(tmp_path, 1, _tree())
    wrong = {"only": jnp.zeros((3,))}
    with pytest.raises(ValueError, match="leaves"):
        ckpt.restore(tmp_path, jax.eval_shape(lambda: wrong))


def test_elastic_restore_with_shardings(tmp_path):
    """Restore placing leaves with explicit (different-mesh) shardings."""
    t = _tree()
    ckpt.save(tmp_path, 1, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(
        lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()), t)
    got, _ = ckpt.restore(tmp_path, jax.eval_shape(lambda: t), shardings=sh)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_falls_back_when_pointer_stale(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 1, t)
    ckpt.save(tmp_path, 2, t)
    (Path(tmp_path) / "LATEST").write_text("99")     # stale pointer
    assert ckpt.latest_step(tmp_path) == 2


def test_train_loop_auto_resume(tmp_path):
    """A restarted loop continues from the checkpointed step (the whole
    node-failure recovery story, end to end on a reduced model)."""
    from repro.configs import ARCHS, reduce_config
    from repro.data import DataConfig
    from repro.launch.mesh import make_host_mesh
    from repro.train.train_loop import TrainLoop, TrainLoopConfig

    cfg = reduce_config(ARCHS["qwen3-8b"])
    mesh = make_host_mesh(model=1)
    mk = lambda steps: TrainLoop(
        cfg, mesh,
        loop_cfg=TrainLoopConfig(total_steps=steps, log_every=100,
                                 ckpt_every=2, ckpt_dir=str(tmp_path),
                                 auto_resume=True),
        data_cfg=DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2))
    s1 = mk(4).run()
    assert s1.step == 4
    loop2 = mk(6)
    s2 = loop2.run()
    assert s2.step == 6
    assert any(e["event"] == "resumed" and e["step"] == 4
               for e in loop2.events)
