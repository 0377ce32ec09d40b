"""The one general generator: it reads a traffic mix's parameters and
drives the program with them.  Two kinds of mix exist:

``closed_loop``
    The configuration's public op, called back to back on one set of
    inputs, with at most ``in_flight`` calls enqueued.  A call is made once
    the call ``in_flight`` before it has finished, so the device always has
    the next call waiting and the host never runs far ahead.

``sessions``
    Measured tuning sessions through the program's ``run_session``, one
    after another, each on the kernel's measured objective
    (``KernelProblem.measured``) with the mix's tuner, budget and worker
    count, journaled to a fresh store.  ``session_seeds`` fixes the
    sessions, so that every run does the same work; the run's seed orders
    them.  The window closes at the end of the first pass over them that
    ends after ``seconds``.

Each kind warms up before its window and returns a :class:`Window`.
"""

from __future__ import annotations

import random
import shutil
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable


@dataclass
class Window:
    seconds: float
    attempted: int
    failed: int
    #: end-to-end values by metric name
    values: dict[str, float]
    #: outputs of the timed path to compare with the reference:
    #: (label, zero-argument call that returns the output or the output)
    outputs: list[tuple[str, Any]] = field(default_factory=list)
    #: numbers compared with a limit, other than the outputs' errors
    counts: dict[str, float] = field(default_factory=dict)
    calls: int = 0
    trials: list = field(default_factory=list)
    #: whether an output that fails the comparison counts as a failure
    outputs_fail: bool = False


def closed_loop(params: dict, *, kernel, inputs: dict, shape: dict,
                interpret: bool, seconds: float, window: Callable[[], Any],
                **_) -> Window:
    import jax

    call = kernel.deploy(inputs, shape, interpret=interpret)
    in_flight = int(params["in_flight"])
    jax.block_until_ready(call())                  # compiles
    for _ in range(3):
        jax.block_until_ready(call())
    pending: deque = deque()
    first = None
    calls = failed = 0
    with window():
        t0 = time.perf_counter()
        while True:
            if len(pending) >= in_flight:
                jax.block_until_ready(pending.popleft())
            try:
                y = call()
            except Exception:              # a failed call counts, and ends
                failed += 1                # the window: its output is lost
                break
            calls += 1
            if first is None:
                first = y
            pending.append(y)
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(list(pending))
        t1 = time.perf_counter()
    last = pending[-1] if pending else None
    elapsed = t1 - t0
    outputs = [("first call", first), ("last call", last)]
    return Window(seconds=elapsed, attempted=calls + failed, failed=failed,
                  values={"kernel_ms": elapsed / max(calls, 1) * 1e3},
                  outputs=[(n, y) for n, y in outputs if y is not None],
                  counts={"failed_calls": failed}, calls=calls)


def sessions(params: dict, *, kernel, inputs: dict, shape: dict,
             seconds: float, seed: int, store_root: Path,
             window: Callable[[], Any], annotate: Callable[[str], Any],
             compile_cache: Callable[[bool], None], **_) -> Window:
    from repro.orchestrator import SessionSpec, SessionStore, run_session

    problem = kernel.problem(shape)
    program_inputs = kernel.program_inputs(inputs, shape)
    measured = problem.measured(program_inputs)

    def spec(s: int, budget: int) -> SessionSpec:
        return SessionSpec(problem=problem.name, tuner=params["tuner"],
                           budget=budget, seed=s,
                           workers=int(params["workers"]),
                           tuner_kwargs=dict(params.get("tuner_kwargs", {})))

    shutil.rmtree(store_root, ignore_errors=True)
    # warm-up: one session of one config, with the compile cache on, so the
    # session code, the tuner and the first compile are paid in set-up
    run_session(spec(0, 1), problem=measured,
                store=SessionStore(store_root / "warmup"))
    order = list(params["session_seeds"])
    random.Random(seed).shuffle(order)

    runs = []
    compile_cache(False)       # every config compiles as a first campaign's
    try:
        with window():
            t0 = time.perf_counter()
            while True:
                for s in order:
                    sp = spec(s, int(params["budget"]))
                    store = SessionStore(store_root / f"w{len(runs):03d}")
                    with annotate("bench.session"):
                        res = run_session(sp, problem=measured, store=store)
                    runs.append((sp, store, res))
                if time.perf_counter() - t0 >= seconds:
                    break
            t1 = time.perf_counter()
    finally:
        compile_cache(True)
    elapsed = t1 - t0

    trials = [t for _, _, res in runs for t in res.trials]
    ok = [t for t in trials if t.valid]
    poisoned = [t for t in trials if t.info.get("poison")]
    refused = [t for t in trials if not t.valid and not t.info.get("poison")]
    lost = 0
    for sp, store, res in runs:
        journal = store.load_journal(sp.session_id, problem.space, sp.arch)
        lost += abs(sp.budget - len(journal)) + abs(sp.budget
                                                    - len(res.trials))
    silent = sum(1 for t in refused if not t.info.get("error"))

    # outputs to compare: each session's best config and a sample of two
    # other measured configs drawn from the seed, each compiled again by
    # the program for the same inputs
    rng = random.Random(seed)
    picked: dict[str, dict] = {}
    for _, _, res in runs:
        if any(t.valid for t in res.trials):
            cfg = res.best.config
            picked.setdefault(_key(cfg), cfg)
    rest = [t.config for t in ok if _key(t.config) not in picked]
    for cfg in rng.sample(rest, min(2, len(rest))):
        picked[_key(cfg)] = cfg
    outputs = [(f"config {k}", _runner(problem, cfg, program_inputs))
               for k, cfg in picked.items()]
    return Window(
        seconds=elapsed, attempted=len(trials), failed=len(poisoned),
        values={"measured_configs_per_s": len(ok) / elapsed},
        outputs=outputs,
        counts={"poisoned": len(poisoned), "journal_lost": lost,
                "refused_silent": silent},
        trials=trials, outputs_fail=True)


def _key(cfg: dict) -> str:
    return "/".join(f"{k}={cfg[k]}" for k in sorted(cfg))


def _runner(problem, cfg: dict, inputs: dict):
    return lambda: problem.compile_kernel(cfg, inputs)()


KINDS = {"closed_loop": closed_loop, "sessions": sessions}


def run(params: dict, **kw) -> Window:
    kind = params["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r} "
                         f"(known: {', '.join(KINDS)})")
    return KINDS[kind](params, **kw)

