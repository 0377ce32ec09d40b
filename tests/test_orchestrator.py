"""Orchestrator subsystem: parallel == serial equivalence, exact resume
without re-evaluation, batched-protocol defaults, fault handling, the
vectorized evaluate_many fast path, and the CLI."""

import json
import math
import random
import threading

import pytest

from repro.core.costmodel import (ARCH_NAMES, KernelFeatures,
                                  estimate_seconds, estimate_seconds_many)
from repro.core.problem import FunctionProblem, Trial, TunableProblem
from repro.core.space import Param, SearchSpace
from repro.core.tuners import (TUNERS, DifferentialEvolution,
                               GeneticAlgorithm, ParticleSwarm, RandomSearch,
                               run_tuner)
from repro.orchestrator import (Campaign, JobQueue, SessionSpec, SessionStore,
                                WorkerPool, make_problem, run_session)
from repro.orchestrator.cli import main as cli_main
from repro.orchestrator.queue import DONE as JOB_DONE
from repro.orchestrator.queue import POISONED
from repro.orchestrator.runner import resume_session

ALL_TUNER_NAMES = sorted(TUNERS)


def _quad_problem(n_params=4, k=8, record=None):
    params = [Param(f"p{i}", tuple(range(k))) for i in range(n_params)]
    space = SearchSpace(params, name="quad")

    def fn(cfg, arch):
        if record is not None:
            record.append(tuple(cfg[f"p{i}"] for i in range(n_params)))
        return 1.0 + sum((cfg[f"p{i}"] - 2) ** 2 for i in range(n_params))

    return FunctionProblem(space, fn, name="quad")


def _traces_equal(a, b):
    return ([t.config for t in a.trials] == [t.config for t in b.trials]
            and [t.objective for t in a.trials] == [t.objective for t in b.trials])


# --------------------------------------------------------------------- #
# parallel session == serial run_tuner
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("tname", ["random", "grid", "local", "annealing",
                                   "surrogate_bo"])
def test_parallel_session_bitforbit_vs_serial(tname):
    """≥4 workers: identical best, trial count, and convergence curve for
    every tuner whose ask stream is batch-invariant (the acceptance
    criterion; population tuners intentionally switch to generational
    batches and are covered separately)."""
    prob = _quad_problem()
    serial = run_tuner(TUNERS[tname](prob.space, seed=3), prob, budget=40)
    spec = SessionSpec(problem="quad", tuner=tname, budget=40, seed=3,
                       workers=4)
    par = run_session(spec, problem=prob)
    assert _traces_equal(serial, par)
    assert par.best.objective == serial.best.objective
    assert par.best.config == serial.best.config
    assert par.best_curve() == serial.best_curve()


@pytest.mark.parametrize("tname", ALL_TUNER_NAMES)
def test_session_deterministic_across_worker_counts(tname):
    """Batch width is set by the tuner, not the pool, so the trajectory is
    a pure function of the spec — identical at 1 and 8 workers."""
    prob = _quad_problem()

    def go(workers):
        spec = SessionSpec(problem="quad", tuner=tname, budget=30, seed=11,
                           workers=workers)
        return run_session(spec, problem=prob,
                           tuner=TUNERS[tname](prob.space, seed=11))

    assert _traces_equal(go(1), go(8))


def test_unique_false_grid_exhaustion_worker_independent():
    """Even with unique=False (cache hits consume budget) and an exhausted
    grid emitting random fallbacks, the recorded trace must not depend on
    worker count — batch width comes from the tuner, never the pool."""
    prob = _quad_problem(n_params=2, k=4)       # 16-config grid, budget 24

    def go(workers):
        spec = SessionSpec(problem="quad", tuner="grid", budget=24, seed=0,
                           workers=workers, unique=False)
        return run_session(spec, problem=prob)

    a, b = go(1), go(4)
    assert len(a.trials) == len(b.trials)
    assert _traces_equal(a, b)


def test_dedup_budget_semantics_match_serial():
    prob = _quad_problem(n_params=1, k=4)          # tiny space forces dups
    serial = run_tuner(RandomSearch(prob.space, seed=0), prob, budget=50)
    spec = SessionSpec(problem="quad", tuner="random", budget=50, seed=0,
                       workers=4)
    par = run_session(spec, problem=prob)
    assert len(par.trials) == len(serial.trials) == 4


# --------------------------------------------------------------------- #
# batched protocol defaults
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("tname", ALL_TUNER_NAMES)
def test_ask_tell_batch_defaults_equal_serial_protocol(tname):
    """Driving any tuner through ask_batch(1)/tell_batch must be
    indistinguishable from the plain ask/tell loop."""
    prob = _quad_problem()
    a = TUNERS[tname](prob.space, seed=9)
    b = TUNERS[tname](prob.space, seed=9)
    for _ in range(30):
        ca = a.ask()
        cb = b.ask_batch(1)
        assert [ca] == cb
        t = prob.evaluate(ca)
        a.tell(t)
        b.tell_batch([t])


@pytest.mark.parametrize("cls,width", [(GeneticAlgorithm, 20),
                                       (DifferentialEvolution, 20),
                                       (ParticleSwarm, 12)])
def test_population_tuners_native_batch(cls, width):
    """Population tuners expose their population as the safe batch width
    and stay consistent over whole-generation ask/tell cycles."""
    prob = _quad_problem(n_params=3, k=6)
    tuner = cls(prob.space, seed=4)
    assert tuner.max_parallel_asks == width
    best = math.inf
    for _ in range(6):                      # 6 generations
        cfgs = tuner.ask_batch(width)
        assert len(cfgs) == width
        assert all(prob.space.satisfies(c) for c in cfgs)
        trials = prob.evaluate_many(cfgs)
        tuner.tell_batch(trials)
        best = min(best, min(t.objective for t in trials))
    assert best < 4.0                       # made real progress on the quad


def test_population_session_converges_in_parallel():
    prob = _quad_problem(n_params=3, k=4)   # |S| = 64
    spec = SessionSpec(problem="quad", tuner="genetic", budget=64, seed=1,
                       workers=8)
    res = run_session(spec, problem=prob)
    assert res.best.objective == pytest.approx(1.0)
    curve = res.best_curve()
    assert all(b2 <= b1 + 1e-12 for b1, b2 in zip(curve, curve[1:]))


# --------------------------------------------------------------------- #
# resume
# --------------------------------------------------------------------- #
def test_resume_skips_journaled_configs(tmp_path):
    """Kill-and-resume: the resumed run must re-evaluate nothing from the
    journal and finish bit-for-bit equal to an uninterrupted run."""
    evals = []
    prob = _quad_problem(record=evals)
    store = SessionStore(tmp_path)
    spec = SessionSpec(problem="quad", tuner="random", budget=30, seed=5,
                       workers=4)

    partial = run_session(spec, problem=prob, store=store, stop_after=12)
    # stop_after lands on the next batch boundary (unbounded cap = 16)
    assert len(partial.trials) == 16
    assert store.meta(spec.session_id)["status"] == "interrupted"
    phase1 = list(evals)
    assert len(phase1) == 16

    full = run_session(spec, problem=prob, store=store)
    phase2 = evals[len(phase1):]
    assert len(full.trials) == 30
    assert store.meta(spec.session_id)["status"] == "done"
    # nothing evaluated twice — the journal answered the replayed prefix
    assert not set(phase1) & set(phase2)
    assert len(phase1) + len(phase2) == 30

    ref = run_tuner(RandomSearch(prob.space, seed=5), _quad_problem(),
                    budget=30)
    assert _traces_equal(ref, full)


@pytest.mark.parametrize("tname", ["genetic", "diffevo", "pso", "local",
                                   "annealing", "surrogate_bo"])
def test_resume_exact_for_stateful_tuners(tmp_path, tname):
    """Resume replays the journal through the tuner, reconstructing its RNG
    state: resumed trace == never-interrupted trace, zero re-evaluations.
    stop_after=25 cuts *past* the first generation boundary of the
    population tuners — the case that requires batch-aligned stops.
    surrogate_bo is the rng-stream-contract regression: its ask draws a
    variable-length sequence (candidate pool sampling), which resume must
    replay identically through the model-refit schedule."""
    evals = []
    prob = _quad_problem(record=evals)
    store = SessionStore(tmp_path / tname)
    spec = SessionSpec(problem="quad", tuner=tname, budget=45, seed=2,
                       workers=4)

    run_session(spec, problem=prob, store=store, stop_after=25)
    n1 = len(evals)
    full = run_session(spec, problem=prob, store=store)
    assert not set(evals[:n1]) & set(evals[n1:])

    uninterrupted = run_session(spec, problem=_quad_problem())
    assert _traces_equal(uninterrupted, full)


@pytest.mark.parametrize("stop", [10, 27, 38])
def test_resume_exact_for_batched_surrogate_bo(tmp_path, stop):
    """Batched qLCB asks draw per-slot kappa jitter; the final batch is
    budget-truncated.  Resume must replay the identical draw stream at
    every stop boundary (the rng-stream contract in tuners/base.py)."""
    prob = _quad_problem()
    store = SessionStore(tmp_path / f"bo{stop}")
    spec = SessionSpec(problem="quad", tuner="surrogate_bo", budget=42,
                       seed=3, workers=2,
                       tuner_kwargs={"n_init": 8, "batch_width": 4})
    run_session(spec, problem=prob, store=store, stop_after=stop)
    resumed = run_session(spec, problem=prob, store=store)
    uninterrupted = run_session(spec, problem=_quad_problem())
    assert _traces_equal(uninterrupted, resumed)


def test_resume_session_api_and_torn_journal(tmp_path):
    """A crash mid-append tears one journal line; records appended after
    the tear must survive a *second* resume (no gluing, no truncation)."""
    evals = []
    prob = _quad_problem(record=evals)
    store = SessionStore(tmp_path)
    spec = SessionSpec(problem="toy_quad", tuner="random", budget=40, seed=0,
                       workers=2)
    run_session(spec, problem=prob, store=store, stop_after=8)
    # simulate a crash mid-append: torn, newline-less final line
    jp = store._journal_path(spec.session_id)
    with open(jp, "a") as f:
        f.write('{"k": 123, "c": [')
    run_session(spec, problem=prob, store=store, stop_after=20)
    n2 = len(evals)
    assert n2 > 16                # fresh records landed after the tear
    full = resume_session(spec.session_id, store)
    assert len(full.trials) == 40
    assert store.meta(spec.session_id)["status"] == "done"
    # the final resume re-evaluates nothing journaled before or after the tear
    assert not set(evals[:n2]) & set(evals[n2:])


def test_finished_session_publishes_trace(tmp_path):
    store = SessionStore(tmp_path)
    prob = _quad_problem()
    spec = SessionSpec(problem="quad", tuner="random", budget=15, seed=1,
                       workers=2)
    res = run_session(spec, problem=prob, store=store)
    table = store.tables.get("quad", "v5e", f"session_{spec.session_id}")
    assert len(table) == len(res.trials)
    assert table.best()[1] == res.best.objective
    assert table.meta["tuner"] == "random"


# --------------------------------------------------------------------- #
# fault handling
# --------------------------------------------------------------------- #
def test_poison_config_marked_invalid_after_retries():
    params = [Param("a", (0, 1, 2, 3))]
    space = SearchSpace(params, name="poison")
    attempts = {}
    lock = threading.Lock()

    def fn(cfg, arch):
        if cfg["a"] == 2:
            with lock:
                attempts["n"] = attempts.get("n", 0) + 1
            raise RuntimeError("kaboom")
        return float(cfg["a"] + 1)

    prob = FunctionProblem(space, fn, name="poison")
    spec = SessionSpec(problem="poison", tuner="grid", budget=4, seed=0,
                       workers=2)
    res = run_session(spec, problem=prob, max_retries=2)
    assert len(res.trials) == 4
    bad = [t for t in res.trials if not t.valid]
    assert len(bad) == 1
    assert bad[0].config["a"] == 2
    assert bad[0].info.get("poison") is True
    # one chunked attempt, then first try + 2 retries on the isolation path
    assert attempts["n"] == 4
    assert res.best.objective == 1.0        # the rest of the grid survived


def test_transient_failure_requeued_and_recovered():
    params = [Param("a", tuple(range(6)))]
    space = SearchSpace(params, name="flaky")
    failed_once = set()
    lock = threading.Lock()

    def fn(cfg, arch):
        with lock:
            if cfg["a"] not in failed_once:
                failed_once.add(cfg["a"])
                raise OSError("transient worker death")
        return float(cfg["a"])

    prob = FunctionProblem(space, fn, name="flaky")
    spec = SessionSpec(problem="flaky", tuner="grid", budget=6, seed=0,
                       workers=3)
    res = run_session(spec, problem=prob, max_retries=1)
    assert len(res.trials) == 6
    assert all(t.valid for t in res.trials)
    assert res.best.objective == 0.0


class _WorkerKiller(TunableProblem):
    """Picklable problem whose a==1 config kills its worker process."""

    name = "killer"

    def __init__(self):
        super().__init__(SearchSpace([Param("a", (0, 1, 2, 3))], name="k"))

    def evaluate(self, config, arch="v5e"):
        if config["a"] == 1:
            import os
            os._exit(13)               # simulated OOM/segfault
        return Trial(config, float(config["a"] + 1), arch)


def test_process_worker_death_poisons_config_not_session():
    """A config that takes down its worker process must end up poisoned
    while the session completes on a rebuilt pool."""
    prob = _WorkerKiller()
    spec = SessionSpec(problem="killer", tuner="grid", budget=4, seed=0,
                       workers=2)
    res = run_session(spec, problem=prob, mode="process", max_retries=1)
    assert len(res.trials) == 4
    bad = [t for t in res.trials if not t.valid]
    assert [t.config["a"] for t in bad] == [1]
    assert bad[0].info.get("poison") is True
    ok = sorted(t.objective for t in res.trials if t.valid)
    assert ok == [1.0, 3.0, 4.0]


def test_session_marked_failed_on_crash(tmp_path):
    store = SessionStore(tmp_path)
    prob = _quad_problem()
    spec = SessionSpec(problem="quad", tuner="random", budget=20, seed=0,
                       workers=2)

    def boom(res):
        raise RuntimeError("driver crash")

    with pytest.raises(RuntimeError, match="driver crash"):
        run_session(spec, problem=prob, store=store, on_batch=boom)
    assert store.meta(spec.session_id)["status"] == "failed"
    # the journaled batch survives: a later resume just continues
    full = run_session(spec, problem=prob, store=store)
    assert len(full.trials) == 20
    assert store.meta(spec.session_id)["status"] == "done"


def test_jobqueue_retry_cap_and_poison():
    q = JobQueue(max_retries=2)
    q.submit(7, {"a": 1})
    job = q.take()
    assert q.fail(job, "err1") is True      # requeued
    job = q.take()
    assert q.fail(job, "err2") is True
    job = q.take()
    assert q.fail(job, "err3") is False     # poisoned
    assert q.job(7).state == POISONED
    assert q.drained()
    # dedup: resubmitting the same key returns the same job
    assert q.submit(7, {"a": 1}).state == POISONED
    q.submit(8, {"a": 2})
    job = q.take()
    q.complete(job, "ok")
    assert q.job(8).state == JOB_DONE
    assert q.counts()[POISONED] == 1


# --------------------------------------------------------------------- #
# vectorized fast path
# --------------------------------------------------------------------- #
class _AnalyticalToy(TunableProblem):
    """Exercises the evaluate_many fast path (features + cost model)."""

    name = "analytical_toy"

    def __init__(self):
        super().__init__(SearchSpace(
            [Param("block", (8, 64, 128, 512)), Param("unroll", (1, 2, 8))],
            name="atoy"))

    def features(self, config, arch):
        b = config["block"]
        return KernelFeatures(
            mxu_flops=2.0 * 4096 ** 3 / 64,
            hbm_bytes=2.0 * 4096 * 4096 * (1 + 512 / b),
            vmem_working_set=b * b * 48.0,
            grid_steps=(4096 / b) ** 2,
            mxu_tile=(b, b, 512), dtype_bytes=2,
            unroll=config["unroll"], inner_trip=b // 8)


def test_evaluate_many_matches_scalar_evaluate():
    prob = _AnalyticalToy()
    cfgs = list(prob.space.enumerate())
    for arch in ARCH_NAMES:
        batch = prob.evaluate_many(cfgs, arch)
        for cfg, t in zip(cfgs, batch):
            ref = prob.evaluate(cfg, arch)
            assert t.objective == ref.objective
            assert t.valid == ref.valid


def test_estimate_seconds_many_matches_scalar():
    rng = random.Random(1)
    feats = [KernelFeatures(
        mxu_flops=rng.choice([0.0, rng.uniform(1e9, 1e13)]),
        vpu_flops=rng.choice([0.0, rng.uniform(1e6, 1e11)]),
        transcendental_ops=rng.uniform(0, 1e9),
        hbm_bytes=rng.uniform(1e3, 1e10),
        vmem_working_set=rng.uniform(0, 220 * 1024 * 1024),
        grid_steps=rng.uniform(1, 1e5),
        mxu_tile=(rng.choice([8, 128, 1000]), rng.choice([8, 512]),
                  rng.choice([32, 4096])),
        dtype_bytes=rng.choice([1, 2, 4]),
        lane_extent=rng.choice([1, 100, 257]),
        sublane_extent=rng.choice([1, 8, 33]),
        unroll=rng.choice([1, 8, 64]), inner_trip=rng.choice([0, 1, 100]),
        serialization=rng.uniform(-0.2, 1.3),
        gather_bytes=rng.choice([0.0, 1e8]),
    ) for _ in range(100)]
    for arch in ARCH_NAMES:
        vec = estimate_seconds_many(feats, arch)
        for f, v in zip(feats, vec):
            s = estimate_seconds(f, arch)
            assert (math.isinf(s) and math.isinf(v)) or s == v
    assert estimate_seconds_many([], "v5e") == []


def test_function_problem_keeps_loop_path():
    calls = []
    prob = _quad_problem(record=calls)
    trials = prob.evaluate_many(prob.space.sample_batch(5, seed=0))
    assert len(trials) == len(calls) == 5


def test_evaluate_many_flags_constraint_violations():
    from repro.core.space import Constraint
    space = SearchSpace([Param("a", (1, 2, 3, 4))],
                        [Constraint("even", lambda c: c["a"] % 2 == 0)])

    class P(_AnalyticalToy):
        def __init__(self):
            TunableProblem.__init__(self, space)

        def features(self, config, arch):
            return KernelFeatures(vpu_flops=1e9, hbm_bytes=1e6)

    trials = P().evaluate_many([{"a": v} for v in (1, 2, 3, 4)])
    assert [t.valid for t in trials] == [False, True, False, True]
    assert trials[0].info["violated"] == ["even"]


# --------------------------------------------------------------------- #
# worker pool
# --------------------------------------------------------------------- #
def test_worker_pool_preserves_order():
    import time as _time
    params = [Param("a", tuple(range(16)))]
    space = SearchSpace(params, name="order")

    def fn(cfg, arch):                       # earlier configs finish later
        _time.sleep((16 - cfg["a"]) * 0.002)
        return float(cfg["a"])

    prob = FunctionProblem(space, fn, name="order")
    with WorkerPool(prob, "v5e", workers=8) as pool:
        trials = pool.evaluate([{"a": i} for i in range(16)])
    assert [t.objective for t in trials] == [float(i) for i in range(16)]


def test_worker_pool_mode_selection():
    from repro.core.problem import MeasuredProblem
    space = SearchSpace([Param("a", (1, 2))], name="m")
    measured = MeasuredProblem(space, build=lambda cfg: (lambda: None))
    assert WorkerPool(measured, "cpu").mode == "thread"
    with pytest.raises(ValueError, match="process mode"):
        WorkerPool(measured, "cpu", mode="process")
    assert WorkerPool(_quad_problem(), "v5e").mode == "thread"
    with pytest.raises(ValueError):
        WorkerPool(_quad_problem(), "v5e", mode="rayon")


# --------------------------------------------------------------------- #
# results cachefile format (optional orjson/zstandard)
# --------------------------------------------------------------------- #
def test_result_table_roundtrip_with_available_codecs(tmp_path):
    from repro.core import results
    from repro.core.results import ResultsDB, ResultTable

    table = ResultTable(problem="p", arch="v5e", param_names=("a",),
                        configs=[(0,), (1,)], objectives=[1.5, math.inf],
                        protocol="exhaustive", meta={"note": "x"})
    raw = table.to_bytes()
    if results.zstandard is None:
        assert raw[0] == 0x78             # zlib header, not the zstd magic
    else:
        assert raw[:4] == results._ZSTD_MAGIC
    back = ResultTable.from_bytes(raw)
    assert back.configs == table.configs
    assert back.objectives == table.objectives

    db = ResultsDB(tmp_path)
    db.put(table)
    assert db.get("p", "v5e", "exhaustive").objectives == table.objectives


def test_zlib_cachefile_loads_regardless_of_zstd():
    """A stdlib-written file must load on any install (format sniffing)."""
    import zlib

    from repro.core.results import _load
    payload = json.dumps({"ok": 1}).encode()
    assert _load(zlib.compress(payload, 6)) == {"ok": 1}


def test_zstd_cachefile_fails_loudly_without_zstandard():
    from repro.core import results
    if results.zstandard is not None:
        pytest.skip("zstandard installed: the fast path handles this")
    with pytest.raises(RuntimeError, match="zstd"):
        results._load(results._ZSTD_MAGIC + b"\x00\x01")


# --------------------------------------------------------------------- #
# sessions, campaigns, CLI
# --------------------------------------------------------------------- #
def test_session_spec_identity_and_roundtrip():
    a = SessionSpec(problem="gemm", tuner="genetic", budget=100, seed=0)
    b = SessionSpec.from_json(json.loads(json.dumps(a.to_json())))
    assert a.session_id == b.session_id
    assert SessionSpec(problem="gemm", tuner="genetic", budget=100,
                       seed=1).session_id != a.session_id


def test_registry_toy_problems():
    prob = make_problem("toy_rastrigin")
    assert prob.space.cardinality == 10 ** 4
    with pytest.raises(KeyError):
        make_problem("nope")


def test_campaign_grid_runs_and_resumes(tmp_path):
    store = SessionStore(tmp_path)
    camp = Campaign.grid(problems=["toy_quad"], tuners=["random", "genetic"],
                         seeds=range(2), budget=25, workers=2)
    assert len(camp) == 4
    results = camp.run(store)
    assert len(results) == 4
    assert camp.done(store)
    rows = camp.status(store)
    assert all(r["status"] == "done" and r["evaluated"] == 25 for r in rows)
    # second run is a pure journal replay: same results, no new evaluations
    again = camp.run(store)
    for sid in results:
        assert _traces_equal(results[sid], again[sid])


# --------------------------------------------------------------------- #
# journal v2: row-native records, info round-trip, v1 compat
# --------------------------------------------------------------------- #
def test_journal_v2_records_are_row_native(tmp_path):
    store = SessionStore(tmp_path)
    prob = _quad_problem()
    spec = SessionSpec(problem="quad", tuner="random", budget=12, seed=7,
                       workers=2)
    run_session(spec, problem=prob, store=store)
    lines = store._journal_path(spec.session_id).read_text().splitlines()
    recs = [json.loads(l) for l in lines]
    assert recs
    for rec in recs:
        assert set(rec) <= {"k", "o", "v", "i"}     # no "c" column
        assert rec["k"] == prob.space.flat_index(
            prob.space.from_flat_index(rec["k"]))


def test_journal_v2_resume_replays_fault_info(tmp_path):
    """The satellite bug: poison markers (poison/attempts/error) must
    survive the journal round-trip, so a resumed trace is info-identical
    to the never-interrupted run."""
    params = [Param("a", tuple(range(24)))]
    space = SearchSpace(params, name="poisonj")

    def fn(cfg, arch):
        if cfg["a"] % 5 == 2:                     # several poison configs
            raise RuntimeError(f"kaboom {cfg['a']}")
        return float(cfg["a"] + 1)

    def mk():
        return FunctionProblem(SearchSpace([Param("a", tuple(range(24)))],
                                           name="poisonj"), fn,
                               name="poisonj")

    store = SessionStore(tmp_path)
    spec = SessionSpec(problem="poisonj", tuner="grid", budget=24, seed=0,
                       workers=2)
    run_session(spec, problem=mk(), store=store, stop_after=10,
                max_retries=1)
    resumed = run_session(spec, problem=mk(), store=store, max_retries=1)
    uninterrupted = run_session(spec, problem=mk(), max_retries=1)

    assert _traces_equal(uninterrupted, resumed)
    assert [t.info for t in resumed.trials] == \
           [t.info for t in uninterrupted.trials]
    poisoned = [t for t in resumed.trials if t.info.get("poison")]
    assert len(poisoned) == 5                      # a in {2,7,12,17,22}
    for t in poisoned:
        assert t.info["attempts"] == 2
        assert "kaboom" in t.info["error"]


def test_journal_v1_records_still_load(tmp_path):
    """A v1 journal (explicit encoded-config column) written by an older
    build must resume exactly."""
    evals = []
    prob = _quad_problem(record=evals)
    store = SessionStore(tmp_path)
    spec = SessionSpec(problem="quad", tuner="random", budget=30, seed=5,
                       workers=4)
    run_session(spec, problem=prob, store=store, stop_after=12)
    jp = store._journal_path(spec.session_id)

    # rewrite the journal in the v1 format
    v1_lines = []
    for line in jp.read_text().splitlines():
        rec = json.loads(line)
        cfg = prob.space.from_flat_index(rec["k"])
        v1 = {"k": rec["k"], "c": list(prob.space.encode(cfg)),
              "o": rec["o"], "v": rec["v"]}
        v1_lines.append(json.dumps(v1, separators=(",", ":")))
    jp.write_text("\n".join(v1_lines) + "\n")

    n1 = len(evals)
    full = run_session(spec, problem=prob, store=store)
    assert len(full.trials) == 30
    assert not set(evals[:n1]) & set(evals[n1:])   # nothing re-evaluated
    ref = run_tuner(RandomSearch(prob.space, seed=5), _quad_problem(),
                    budget=30)
    assert _traces_equal(ref, full)


def test_json_safe_info_filter():
    from repro.orchestrator.store import _json_safe_info

    class Blob:                                    # not JSON-serializable
        pass

    info = {"error": "boom", "poison": True, "attempts": 3,
            "violated": ["c1", "c2"], "nested": {"a": 1.5, "b": [1, "x"]},
            "features": Blob(), "inf": math.inf, "none": None}
    safe = _json_safe_info(info)
    assert safe == {"error": "boom", "poison": True, "attempts": 3,
                    "violated": ["c1", "c2"],
                    "nested": {"a": 1.5, "b": [1, "x"]}, "none": None}
    assert json.loads(json.dumps(safe)) == safe


def test_trial_lazy_config_and_materialize():
    from repro.core.problem import materialize_configs
    prob = _quad_problem(n_params=2, k=4)
    space = prob.space
    space.compile_eagerly()
    lazy = [Trial(None, 1.0, "v5e", row=r, space=space) for r in (3, 7, 11)]
    assert all(t._config is None for t in lazy)
    assert [t.row for t in lazy] == [3, 7, 11]
    materialize_configs(lazy)
    for t, r in zip(lazy, (3, 7, 11)):
        assert t._config is not None
        assert t.config == space.from_flat_index(r)
    with pytest.raises(ValueError):
        Trial(None, 1.0, "v5e")                    # lazy needs row+space
    # eager trials may carry their row too (journal/publish fast path)
    t = Trial({"a": 1}, 2.0, "v5e", row=9, space=space)
    assert t.config == {"a": 1} and t.row == 9


# --------------------------------------------------------------------- #
# empty ask == finished (the cfgs[0] crash)
# --------------------------------------------------------------------- #
def _stub_tuner_class(rows_mode: bool):
    from repro.core.tuners.base import Tuner

    class Stub(Tuner):
        """Returns one short batch, then empty asks (exhaustion flipping
        mid-batch) — the dict path used to crash on ``cfgs[0]``."""
        name = "stub"
        max_parallel_asks = None

        def __init__(self, space, seed=0):
            super().__init__(space, seed)
            self._served = False
            if not rows_mode:
                self._comp = None      # force the dict path

        def ask_scalar(self):
            return self.space.from_flat_index(0)

        def ask_batch(self, n):
            if self._served:
                return []
            self._served = True
            return [self.space.from_flat_index(i) for i in range(3)]

        def ask_rows(self, n):
            if self._served:
                return []
            self._served = True
            return [0, 1, 2]

    return Stub


@pytest.mark.parametrize("rows_mode", [False, True])
def test_empty_ask_batch_treated_as_finished(tmp_path, rows_mode):
    prob = _quad_problem(n_params=2, k=4)
    store = SessionStore(tmp_path)
    spec = SessionSpec(problem="quad", tuner="stub", budget=20, seed=0,
                       workers=2)
    tuner = _stub_tuner_class(rows_mode)(prob.space, seed=0)
    assert tuner.index_native == rows_mode
    res = run_session(spec, problem=prob, tuner=tuner, store=store)
    # the short batch landed, the empty ask ended the session cleanly
    assert len(res.trials) == 3
    assert store.meta(spec.session_id)["status"] == "done"


def test_immediately_empty_ask_is_clean_noop():
    prob = _quad_problem(n_params=2, k=4)
    Stub = _stub_tuner_class(False)
    tuner = Stub(prob.space, seed=0)
    tuner._served = True                           # empty from the first ask
    spec = SessionSpec(problem="quad", tuner="stub", budget=20, seed=0,
                       workers=2)
    res = run_session(spec, problem=prob, tuner=tuner)
    assert res.trials == []


# --------------------------------------------------------------------- #
# publish-before-DONE (the lost-table crash window)
# --------------------------------------------------------------------- #
def test_trace_published_before_done_mark(tmp_path):
    calls = []
    store = SessionStore(tmp_path)
    orig_publish, orig_update = store.publish_trace, store.update_meta
    store.publish_trace = lambda *a, **k: (calls.append("publish"),
                                           orig_publish(*a, **k))[1]
    store.update_meta = lambda sid, **f: (
        calls.append(f"meta:{f.get('status')}") or orig_update(sid, **f))
    prob = _quad_problem()
    spec = SessionSpec(problem="quad", tuner="random", budget=10, seed=1,
                       workers=2)
    run_session(spec, problem=prob, store=store)
    assert "publish" in calls
    assert calls.index("publish") < calls.index("meta:done")


def test_crash_between_publish_and_done_is_resumable(tmp_path):
    """A crash in the publish→DONE window must leave a resumable session
    whose table already exists; resume republishes idempotently and
    finishes DONE."""
    store = SessionStore(tmp_path)
    prob = _quad_problem()
    spec = SessionSpec(problem="quad", tuner="random", budget=10, seed=1,
                       workers=2)
    orig = store.update_meta

    def boom_on_done(sid, **fields):
        if fields.get("status") == "done":
            raise OSError("crash before the DONE mark")
        return orig(sid, **fields)

    store.update_meta = boom_on_done
    with pytest.raises(OSError):
        run_session(spec, problem=prob, store=store)
    # the table survived the crash; the session is not a lost DONE husk
    table = store.tables.get("quad", "v5e", f"session_{spec.session_id}")
    assert len(table) == 10
    assert store.meta(spec.session_id)["status"] == "failed"

    store.update_meta = orig
    res = run_session(spec, problem=prob, store=store)  # == resume_session
    assert len(res.trials) == 10
    assert store.meta(spec.session_id)["status"] == "done"
    table = store.tables.get("quad", "v5e", f"session_{spec.session_id}")
    assert table.best()[1] == res.best.objective


def test_cli_campaign_runs_grid(tmp_path, capsys):
    store_dir = str(tmp_path / "camp_store")
    rc = cli_main(["campaign", "--problems", "toy_quad",
                   "--tuners", "random,genetic", "--archs", "v5e,v4",
                   "--seeds", "0,1", "--budget", "20", "--workers", "2",
                   "--store", store_dir])
    assert rc == 0
    out = capsys.readouterr().out
    assert "8 sessions" in out
    assert out.count("done") == 8
    rc = cli_main(["campaign", "--problems", "nope", "--tuners", "random",
                   "--store", store_dir])
    assert rc == 2
    capsys.readouterr()
    rc = cli_main(["campaign", "--problems", "toy_quad", "--tuners", "zzz",
                   "--store", store_dir])
    assert rc == 2
    capsys.readouterr()


def test_cli_submit_status_resume(tmp_path, capsys):
    store_dir = str(tmp_path / "cli_store")
    rc = cli_main(["submit", "--problem", "toy_quad", "--tuner", "random",
                   "--budget", "18", "--seed", "3", "--workers", "2",
                   "--store", store_dir, "--stop-after", "7"])
    assert rc == 0
    sid = capsys.readouterr().out.split()[1]

    rc = cli_main(["status", "--store", store_dir])
    assert rc == 0
    out = capsys.readouterr().out
    # stop-after 7 rounds up to the 16-wide unbounded batch boundary
    assert sid in out and "interrupted" in out and "16/18" in out

    rc = cli_main(["resume", sid, "--store", store_dir])
    assert rc == 0
    assert "18 trials" in capsys.readouterr().out

    rc = cli_main(["status", sid, "--store", store_dir])
    assert "done" in capsys.readouterr().out and rc == 0

    assert cli_main(["resume", "missing", "--store", store_dir]) == 2
    capsys.readouterr()
    assert cli_main(["submit", "--problem", "toy_quad", "--tuner", "random",
                     "--store", store_dir, "--tuner-kwargs", "{bad"]) == 2
