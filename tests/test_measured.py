"""The measured objective, on the CPU with fake builds.

``MeasuredProblem`` is the objective that times kernels on the chip.  What
it must do there can be checked without one: a config the compiler refuses
is one invalid trial carrying the error (one compile, no retries), each
timing waits for the result, a session measures one config at a time in
the calling thread, and the compile cache lands where it is told.
"""

from __future__ import annotations

import threading
import time

import jax
import pytest

from repro.core.problem import FunctionProblem, MeasuredProblem
from repro.core.space import Param, SearchSpace
from repro.kernels import common
from repro.kernels.attention.space import AttentionProblem
from repro.orchestrator import SessionSpec, SessionStore, run_session
from repro.orchestrator.workers import WorkerPool


def _space():
    return SearchSpace([Param("a", (0, 1, 2, 3))], name="m")


class _Refusing:
    """A build that fails to compile odd configs and counts its calls."""

    def __init__(self):
        self.calls: list[int] = []

    def __call__(self, config):
        self.calls.append(config["a"])
        if config["a"] % 2:
            raise RuntimeError(f"RESOURCE_EXHAUSTED: vmem (a={config['a']})")
        return lambda: None


def test_compile_error_is_one_invalid_trial_without_retries():
    build = _Refusing()
    prob = MeasuredProblem(_space(), build, repeats=1, warmup=0)
    with WorkerPool(prob, "v5e", max_retries=2) as pool:
        trials = pool.evaluate([{"a": a} for a in range(4)])
    assert build.calls == [0, 1, 2, 3]          # one compile per config
    assert [t.valid for t in trials] == [True, False, True, False]
    for t in trials[1::2]:
        assert "RESOURCE_EXHAUSTED" in t.info["error"]
        assert "poison" not in t.info


def test_refused_config_is_journaled_with_its_error(tmp_path):
    store = SessionStore(tmp_path)
    build = _Refusing()
    prob = MeasuredProblem(_space(), build, repeats=1, warmup=0)
    spec = SessionSpec(problem="m", tuner="grid", budget=4, seed=0)
    res = run_session(spec, problem=prob, store=store)
    assert sorted(build.calls) == [0, 1, 2, 3]
    journal = dict(store.load_journal(spec.session_id, prob.space))
    refused = [t for t in journal.values() if not t.valid]
    assert len(refused) == 2 == sum(not t.valid for t in res.trials)
    assert all("vmem" in t.info["error"] for t in refused)


class _DeviceResult:
    """Stands in for an array whose computation is still running."""

    def __init__(self, seconds: float, log: list):
        self.seconds, self.log = seconds, log

    def block_until_ready(self):
        time.sleep(self.seconds)
        self.log.append("ready")
        return self


def test_timing_blocks_on_the_result():
    log: list[str] = []
    prob = MeasuredProblem(
        _space(), lambda c: (lambda: _DeviceResult(0.02, log)),
        repeats=3, warmup=2)
    t = prob.evaluate({"a": 0})
    assert log == ["ready"] * 5                 # every warm-up and repeat
    assert t.valid and t.objective >= 0.02


def test_session_measures_one_config_at_a_time_in_caller():
    active, peak, threads = [0], [0], set()
    lock = threading.Lock()

    def run():
        with lock:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        threads.add(threading.get_ident())
        time.sleep(0.002)
        with lock:
            active[0] -= 1

    prob = MeasuredProblem(_space(), lambda c: run, repeats=2, warmup=1)
    spec = SessionSpec(problem="m", tuner="random", budget=4, seed=0,
                       workers=4)
    res = run_session(spec, problem=prob)
    assert len(res.trials) == 4 and all(t.valid for t in res.trials)
    assert peak[0] == 1
    assert threads == {threading.get_ident()}


def test_process_pool_refuses_measured_problems():
    measured = MeasuredProblem(_space(), lambda c: (lambda: None))
    with pytest.raises(ValueError, match="child process"):
        WorkerPool(measured, "v5e", mode="process")
    analytical = FunctionProblem(_space(), lambda c, arch: 1.0)
    with WorkerPool(analytical, "v5e", mode="process") as pool:
        with pytest.raises(ValueError, match="child process"):
            pool.evaluate([{"a": 0}], problem=measured)


def test_kernel_build_compiles_for_the_device(monkeypatch, tmp_path):
    """``KernelProblem.measured`` compiles the kernel, never interprets it:
    on the CPU the compiler refuses every config, and each refusal is an
    invalid trial that carries the compiler's words."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prob = AttentionProblem()
    measured = prob.measured(prob.make_inputs(jax.random.key(0)))
    t = measured.evaluate(prob.space.sample_distinct(1, seed=0)[0])
    assert not t.valid
    assert "interpret" in t.info["error"]


def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert common.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = common.use_compile_cache()
        assert got == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
    root = common.COMPILE_CACHE_DIR.parents[1]
    assert common.COMPILE_CACHE_DIR == root / "experiments" / "jax_cache"
    assert (root / "src" / "repro" / "kernels" / "common.py").is_file()
