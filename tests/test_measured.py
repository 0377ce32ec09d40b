"""The measured objective, on the CPU with fake builds.

``MeasuredProblem`` is the objective that times kernels on the chip.  What
it must do there can be checked without one: a config the compiler refuses
is one invalid trial carrying the error (one compile, no retries), each
timing waits for the result, a session measures one config at a time in
the calling thread, and the compile cache lands where it is told.  A
build in two stages compiles a batch's next config on a thread while the
current one is measured, and changes no trial.
"""

from __future__ import annotations

import threading
import time

import jax
import pytest

from repro.core import problem as problem_mod
from repro.core.problem import FunctionProblem, MeasuredProblem
from repro.core.space import Param, SearchSpace
from repro.kernels import common
from repro.kernels.attention.space import AttentionProblem
from repro.orchestrator import SessionSpec, SessionStore, run_session
from repro.orchestrator.workers import EvalCancelled, WorkerPool


def _space(n: int = 4):
    return SearchSpace([Param("a", tuple(range(n)))], name="m")


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


def test_kernel_build_records_lower_and_compile(monkeypatch, tmp_path):
    """A compiled kernel's build is two child spans, lowering and the
    backend compile; the CPU compiles the kernel lowered in interpret
    mode."""
    from repro.telemetry import trace as ttrace

    def lower(self, config, inputs):
        arrays, consts = common._split_inputs(inputs)
        return jax.jit(lambda a: self.run_kernel(
            config, {**consts, **a}, interpret=True)).lower(arrays)

    monkeypatch.setattr(common.KernelProblem, "lower_kernel", lower)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prob = AttentionProblem({"hq": 4, "hkv": 2, "tq": 256, "tk": 256,
                             "d": 64})            # the small inputs' shape
    measured = prob.measured(prob.make_inputs(jax.random.key(0)),
                             repeats=1, warmup=1)
    config = prob.space.sample_distinct(1, seed=0)[0]
    with ttrace.tracing():
        t = measured.evaluate(config)
        spans = ttrace.events()
    assert t.valid, t.info
    by = {e["name"]: e for e in spans}
    build = by["kernel.build"]
    for name in ("kernel.lower", "kernel.compile"):
        child = by[name]
        assert child["cat"] == "kernel"
        assert child["depth"] == build["depth"] + 1
        assert build["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= build["ts"] + build["dur"]
    assert by["kernel.lower"]["ts"] < by["kernel.compile"]["ts"]
    measure = by["kernel.measure"]["args"]
    assert measure["key"] == build["args"]["key"]
    assert measure["calls"] == 2 and measure["best_s"] == t.objective


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


class _Stages:
    """A build in two stages, lowering and compile, that notes what runs on
    which thread and when.  It refuses the configs of ``refuse_lower`` at
    lowering and those of ``refuse_compile`` at compile; the callable of a
    config in ``fail_once`` raises on its first call."""

    def __init__(self, refuse_lower=(), refuse_compile=(), fail_once=(),
                 compile_s=0.0, call_s=0.0, on_call=None):
        self.refuse_lower, self.refuse_compile = refuse_lower, refuse_compile
        self.fail_once = set(fail_once)
        self.compile_s, self.call_s, self.on_call = compile_s, call_s, on_call
        self.log: list[tuple[str, int, int, float]] = []
        self.compiling = 0
        self._lock = threading.Lock()

    def _note(self, event: str, a: int) -> None:
        with self._lock:
            self.log.append((event, a, threading.get_ident(),
                             time.perf_counter()))

    def lower(self, config):
        self._note("lower", config["a"])
        if config["a"] in self.refuse_lower:
            raise RuntimeError(f"lowering refused (a={config['a']})")
        return config["a"]

    def compile(self, a):
        with self._lock:
            self.compiling += 1
        try:
            self._note("compile", a)
            time.sleep(self.compile_s)
            if a in self.refuse_compile:
                raise RuntimeError(f"RESOURCE_EXHAUSTED: vmem (a={a})")
        finally:
            with self._lock:
                self.compiling -= 1

        def run():
            self._note("call", a)
            time.sleep(self.call_s)
            if self.on_call is not None:
                self.on_call(a)
            if a in self.fail_once:
                self.fail_once.discard(a)
                raise RuntimeError(f"device lost (a={a})")
        return run

    def problem(self, n: int = 4, **kw) -> MeasuredProblem:
        return MeasuredProblem(_space(n), lower=self.lower,
                               compile=self.compile, repeats=2, warmup=1,
                               **kw)

    def one_callable(self, n: int = 4) -> MeasuredProblem:
        return MeasuredProblem(_space(n),
                               lambda c: self.compile(self.lower(c)),
                               repeats=2, warmup=1)

    def first(self, event: str, a: int) -> tuple[int, float]:
        """Thread and time of the first ``event`` of config ``a``."""
        return next((tid, t) for e, x, tid, t in self.log
                    if e == event and x == a)

    def count(self, event: str, a: int) -> int:
        return sum(1 for e, x, *_ in self.log if e == event and x == a)

    def compiled_ahead(self) -> set[int]:
        """Configs compiled off the calling thread."""
        caller = threading.get_ident()
        return {a for e, a, tid, _ in self.log
                if e == "compile" and tid != caller}


def _trials(trials):
    return [(t.config, t.valid, t.info.get("error"), t.info.get("poison"))
            for t in trials]


def test_next_compile_starts_before_the_current_config_is_called():
    from repro.telemetry import trace as ttrace

    stages = _Stages(compile_s=0.05, call_s=0.002)
    caller = threading.get_ident()
    with ttrace.tracing(), WorkerPool(stages.problem(), "v5e") as pool:
        trials = pool.evaluate([{"a": a} for a in range(4)])
        spans = ttrace.events()
    assert [t.valid for t in trials] == [True] * 4
    assert stages.first("compile", 0)[0] == caller        # the first, inline
    for a in range(3):
        tid, started = stages.first("compile", a + 1)
        assert tid != caller
        assert started < stages.first("call", a)[1]
        assert stages.first("lower", a + 1)[0] == caller
    assert {tid for e, _, tid, _ in stages.log if e == "call"} == {caller}
    compiles = [e for e in spans if e["name"] == "kernel.compile"]
    assert [c["args"]["ahead"] for c in compiles] == [0, 1, 1, 1]
    assert [c["args"]["key"] for c in compiles] == [f"a={a}" for a in range(4)]
    # each compile ahead is open before the measurement it hides behind,
    # which is then the innermost span on the measuring thread
    measures = [e for e in spans if e["name"] == "kernel.measure"]
    for c, m in zip(compiles[1:], measures):
        assert c["ts"] < m["ts"] < c["ts"] + c["dur"]


def test_refusals_ahead_give_the_serial_trials_with_one_compile_each():
    configs = [{"a": a} for a in range(8)]
    kw = dict(refuse_lower={2}, refuse_compile={5, 7})
    serial, ahead = _Stages(**kw), _Stages(**kw)
    with WorkerPool(serial.one_callable(8), "v5e") as pool:
        want = pool.evaluate(configs)
    with WorkerPool(ahead.problem(8), "v5e") as pool:
        got = pool.evaluate(configs)
    assert _trials(got) == _trials(want)
    assert [t.valid for t in got] == [True, True, False, True, True, False,
                                      True, False]
    assert "lowering refused" in got[2].info["error"]
    assert "RESOURCE_EXHAUSTED" in got[5].info["error"]
    for a in range(8):
        assert ahead.count("lower", a) == 1
        assert ahead.count("compile", a) == (a != 2)
    # 0, 3 and 6 follow no measured config, so they compile inline
    assert ahead.compiled_ahead() == {1, 4, 5, 7}


@pytest.mark.parametrize("n_configs,two_stage", [(1, True), (4, False)])
def test_no_compile_thread_for_one_config_or_one_callable(
        monkeypatch, n_configs, two_stage):
    made = []

    class Counting(problem_mod.ThreadPoolExecutor):
        def __init__(self, *a, **kw):
            made.append(1)
            super().__init__(*a, **kw)

    monkeypatch.setattr(problem_mod, "ThreadPoolExecutor", Counting)
    stages = _Stages()
    prob = stages.problem() if two_stage else stages.one_callable()
    with WorkerPool(prob, "v5e", workers=4) as pool:
        trials = pool.evaluate([{"a": a} for a in range(n_configs)])
    assert [t.valid for t in trials] == [True] * n_configs
    assert made == []
    assert {tid for _, _, tid, _ in stages.log} == {threading.get_ident()}


def test_cancel_waits_for_the_compile_in_flight():
    cancel = threading.Event()

    def on_call(a):
        if a == 1:
            cancel.set()

    stages = _Stages(compile_s=0.05, on_call=on_call)
    with WorkerPool(stages.problem(), "v5e") as pool:
        with pytest.raises(EvalCancelled):
            pool.evaluate([{"a": a} for a in range(4)], cancel=cancel)
        assert pool.stats["cancelled"] == 1
    assert stages.compiling == 0                  # none left running
    assert stages.count("compile", 2) == 1        # started ahead, discarded
    assert stages.count("call", 2) == 0
    assert stages.count("lower", 3) == 0


def test_measurement_error_is_retried_with_an_inline_rebuild():
    stages = _Stages(fail_once={1, 2})      # 2 is the batch's last config
    caller = threading.get_ident()
    with WorkerPool(stages.problem(), "v5e", max_retries=2) as pool:
        trials = pool.evaluate([{"a": a} for a in range(3)])
    assert [t.valid for t in trials] == [True] * 3
    for a in (1, 2):
        assert "poison" not in trials[a].info
        builds = [tid for e, x, tid, _ in stages.log
                  if e == "compile" and x == a]
        assert len(builds) == 2
        assert builds[0] != caller and builds[1] == caller


def test_random_session_journal_is_the_same_with_compile_ahead(tmp_path):
    def journal(prob, name):
        store = SessionStore(tmp_path / name)
        spec = SessionSpec(problem="m", tuner="random", budget=8, seed=3)
        run_session(spec, problem=prob, store=store)
        return [(t.config, t.valid)
                for _, t in store.load_journal(spec.session_id, prob.space)]

    serial, ahead = _Stages(refuse_compile={3}), _Stages(refuse_compile={3})
    want = journal(serial.one_callable(16), "serial")
    got = journal(ahead.problem(16), "ahead")
    assert got == want and len(got) == 8
    assert ahead.compiled_ahead()
