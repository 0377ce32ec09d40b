"""Worker pool: parallel config evaluation with fault isolation.

Two execution modes for analytical problems:

* ``thread`` (the default) — chunks of the batch go through
  ``TunableProblem.evaluate_many`` (the vectorized fast path), one chunk
  per worker thread.
* ``process`` — the same chunks in child processes.  The problem must be
  picklable.

A :class:`MeasuredProblem` takes neither: it is measured in the calling
thread, one config at a time.  The device belongs to the process that
holds it, so a child process cannot measure on it, and two configs timed
at once would share it.  A process-mode pool refuses measured problems.

Fault handling: a chunk that raises is retried config-by-config through a
:class:`JobQueue`; a config that keeps raising past the retry cap is
*poisoned* — returned as an invalid :class:`Trial` carrying the error, so
one bad config can never wedge a session.

Shared pools: every evaluation entry point takes per-call ``problem=`` and
``arch=`` overrides, so one pool (one executor, one set of warm workers)
can serve every session of a campaign grid regardless of which problem or
architecture each session tunes.  The arch-shared form
``evaluate_rows(rows, archs=[...])`` evaluates each row ONCE via
``TunableProblem.trials_for_rows_archs`` (one decode + one set of value
columns shared by all architectures) and returns per-arch trial lists —
the portability-campaign fast path.
"""

from __future__ import annotations

import contextlib
import json
import math
import threading
import time
from concurrent.futures import (FIRST_COMPLETED, BrokenExecutor, Executor,
                                ProcessPoolExecutor, ThreadPoolExecutor, wait)
from typing import Sequence

from ..core.problem import MeasuredProblem, Trial, TunableProblem
from ..core.space import Config
from ..telemetry.trace import span
from . import chaos
from .queue import DONE, JobQueue


class EvalCancelled(Exception):
    """An in-flight batch was abandoned on purpose (lease lost): the
    worker's result would be rejected by completion-requires-lease, so
    finishing the evaluation is pure waste.  Raised out of the pool's
    wait loops when the caller's cancel event is set."""


_MEASURED_IN_CHILD = (
    "a MeasuredProblem cannot run in process mode: it times kernels on the "
    "device this process holds, and a child process cannot use that device")

#: thread-mode minimum chunk size: splitting a small analytical batch
#: across every worker forfeits the columnar evaluation path (below
#: ``problem._COLUMNAR_MIN`` rows per chunk) for pure scheduler overhead.
#: Results are chunking-independent (the compiled-path equivalence
#: property), so this is a wall-clock knob only.
_THREAD_CHUNK_FLOOR = 32


def _evaluate_chunk(problem: TunableProblem, configs: list[Config],
                    arch: str) -> list[Trial]:
    # module-level so the process pool can pickle it.  Chunk spans record
    # in the executing thread's (or, for process mode, the child's own)
    # ring buffer — per-chunk, never per-config.  chaos site eval.hang
    # simulates a wedged measurement *inside* the chunk — it pins this
    # executor thread exactly like a hung kernel build would.
    chaos.sleep(chaos.EVAL_HANG)
    with span("pool.chunk", cat="pool", n=len(configs), arch=arch):
        return problem.evaluate_many(configs, arch)


def _evaluate_rows_chunk(problem: TunableProblem, rows: list[int],
                         arch: str) -> list[Trial]:
    chaos.sleep(chaos.EVAL_HANG)
    with span("pool.chunk", cat="pool", n=len(rows), arch=arch):
        return problem.trials_for_rows(rows, arch)


def _evaluate_rows_archs_chunk(problem: TunableProblem, rows: list[int],
                               archs: tuple[str, ...]) -> list[list[Trial]]:
    chaos.sleep(chaos.EVAL_HANG)
    with span("pool.chunk", cat="pool", n=len(rows), archs=len(archs)):
        return problem.trials_for_rows_archs(rows, archs)


def _evaluate_one(problem: TunableProblem, config: Config, arch: str) -> Trial:
    chaos.sleep(chaos.EVAL_HANG)
    return problem.evaluate(config, arch)


class WorkerPool:
    """Evaluates batches of configs for one problem on one arch (both
    overridable per call for shared campaign pools).

    Results always come back in input order regardless of completion order —
    the property the session runner relies on for determinism.
    """

    def __init__(self, problem: TunableProblem, arch: str, workers: int = 4,
                 mode: str = "auto", max_retries: int = 2,
                 job_timeout_s: float | None = None):
        if mode == "auto":
            mode = "thread"
        if mode not in ("thread", "process"):
            raise ValueError(f"unknown worker mode {mode!r}")
        if mode == "process" and isinstance(problem, MeasuredProblem):
            raise ValueError(_MEASURED_IN_CHILD)
        self.problem = problem
        self.arch = arch
        self.workers = max(1, int(workers))
        self.mode = mode
        self.max_retries = max_retries
        # the evaluation watchdog: bounds the chunked fast path as one
        # batch deadline, then each per-config retry attempt separately —
        # a config whose *every* attempt exceeds it terminates as a
        # timeout-poison trial (info: poison + timeout) instead of
        # pinning the pool until the broker reaps the lease
        self.job_timeout_s = job_timeout_s
        #: watchdog observability: bumped on every timed-out chunk/attempt
        #: and every cancelled batch (read by BrokerWorker job metrics)
        self.stats = {"timeouts": 0, "cancelled": 0}
        self._ex: Executor | None = None

    # -- lifecycle -------------------------------------------------------- #
    def _executor(self) -> Executor:
        if self._ex is None:
            cls = (ProcessPoolExecutor if self.mode == "process"
                   else ThreadPoolExecutor)
            self._ex = cls(max_workers=self.workers)
        return self._ex

    def _rebuild(self) -> Executor:
        """Replace a broken executor (a worker OOM/segfault kills the whole
        ProcessPoolExecutor, not just its job)."""
        if self._ex is not None:
            self._ex.shutdown(wait=False)
            self._ex = None
        return self._executor()

    def close(self) -> None:
        if self._ex is not None:
            self._ex.shutdown(wait=True)
            self._ex = None

    def __enter__(self) -> "WorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- evaluation ------------------------------------------------------- #
    def evaluate_rows(self, rows: Sequence[int], arch: str | None = None,
                      *, archs: Sequence[str] | None = None,
                      problem: TunableProblem | None = None,
                      cancel: threading.Event | None = None):
        """Row-native :meth:`evaluate`: valid compiled-space rows in, trials
        out — same ordering/fault-isolation guarantees, but the chunks run
        ``TunableProblem.trials_for_rows`` (value columns straight from the
        code matrix, no per-config dict work; configs stay lazy).

        With ``archs=`` the call becomes arch-shared: each row is evaluated
        exactly once — one decode, one set of value columns, one feature
        build when ``arch_independent_features`` — and the return value is
        ``{arch: list[Trial]}`` with every list aligned with ``rows``.
        Bit-identical to one single-arch call per architecture (the
        compiled-path equivalence property), at ~1/len(archs) the work.
        """
        problem = problem or self.problem
        if archs is not None:
            return self._evaluate_rows_archs(rows, tuple(archs), problem,
                                             cancel=cancel)
        rows = [int(r) for r in rows]
        if not rows:
            return []
        if self.mode == "process" or isinstance(problem, MeasuredProblem):
            # process chunks and measured problems take configs: decode
            # the rows once and go through evaluate
            cfgs = self._rows_to_configs(rows, problem)
            return self.evaluate(cfgs, arch, problem=problem, cancel=cancel)
        return self._evaluate_chunked(rows, arch or self.arch,
                                      _evaluate_rows_chunk,
                                      self._rows_to_configs, problem,
                                      cancel=cancel)

    def _rows_to_configs(self, rows: list[int],
                         problem: TunableProblem | None = None) -> list[Config]:
        problem = problem or self.problem
        comp = problem.space.compiled()
        if comp is not None:
            return comp.decode_many(rows)
        return [problem.space.from_flat_index(int(r)) for r in rows]

    def evaluate(self, configs: Sequence[Config], arch: str | None = None,
                 *, problem: TunableProblem | None = None,
                 cancel: threading.Event | None = None) -> list[Trial]:
        """Evaluate ``configs`` in parallel; ordered, fault-isolated."""
        configs = list(configs)
        if not configs:
            return []
        problem = problem or self.problem
        if isinstance(problem, MeasuredProblem):
            return self._measure(configs, arch or self.arch, problem, cancel)
        return self._evaluate_chunked(configs, arch or self.arch,
                                      _evaluate_chunk, None, problem,
                                      cancel=cancel)

    def _measure(self, configs: list[Config], arch: str,
                 problem: MeasuredProblem,
                 cancel: threading.Event | None = None) -> list[Trial]:
        """Measure ``configs`` in the calling thread, one at a time.

        A config whose measurement raises is retried, then poisoned, as on
        the pool's retry path; a config the compiler refuses is already an
        invalid trial (``MeasuredProblem.evaluate``) and costs one compile.
        The watchdog does not apply: a running measurement cannot be
        interrupted from its own thread.

        A batch of two configs or more, whose build has two stages,
        compiles ahead (``MeasuredProblem.compiling_ahead``): the next
        config's backend compile runs on a thread while this one measures
        the current config.  A retry builds inline."""
        if self.mode == "process":
            raise ValueError(_MEASURED_IN_CHILD)
        out: list[Trial] = []
        ahead = problem.two_stage and len(configs) > 1
        with span("pool.evaluate", cat="pool", n=len(configs), arch=arch,
                  mode="inline"), \
                (problem.compiling_ahead() if ahead
                 else contextlib.nullcontext()) as plan:
            for i, cfg in enumerate(configs):
                if cancel is not None and cancel.is_set():
                    self.stats["cancelled"] += 1
                    raise EvalCancelled("batch abandoned (lease lost)")
                if plan is not None:
                    plan.following = (configs[i + 1] if i + 1 < len(configs)
                                      else None)
                for attempt in range(1, self.max_retries + 2):
                    try:
                        out.append(_evaluate_one(problem, cfg, arch))
                        break
                    except Exception as e:
                        err = repr(e)
                else:
                    out.append(Trial(cfg, math.inf, arch, valid=False,
                                     info={"error": err, "poison": True,
                                           "attempts": attempt}))
        return out

    # -- arch-shared evaluation ------------------------------------------- #
    def _evaluate_rows_archs(self, rows: Sequence[int], archs: tuple[str, ...],
                             problem: TunableProblem,
                             cancel: threading.Event | None = None
                             ) -> dict[str, list[Trial]]:
        rows = [int(r) for r in rows]
        if not rows:
            return {a: [] for a in archs}
        if self.mode == "process" or isinstance(problem, MeasuredProblem):
            # measured problems measure per architecture by definition —
            # there is nothing to share beyond the one decode
            cfgs = self._rows_to_configs(rows, problem)
            return {a: self.evaluate(cfgs, a, problem=problem, cancel=cancel)
                    for a in archs}

        ex = self._executor()
        deadline = (None if self.job_timeout_s is None
                    else time.monotonic() + self.job_timeout_s)
        with span("pool.evaluate", cat="pool", n=len(rows),
                  archs=len(archs), mode=self.mode):
            done, retry, broken = self._run_chunks(
                rows, lambda chunk: ex.submit(_evaluate_rows_archs_chunk,
                                              problem, chunk, archs),
                cancel=cancel, deadline=deadline)
        out: dict[str, list] = {a: [None] * len(rows) for a in archs}
        for lo, hi, per_arch in done:
            for a, trials in zip(archs, per_arch):
                out[a][lo:hi] = trials

        if retry:
            # per-row isolation: decode just the failing rows once, then run
            # the per-config retry/poison machinery independently per arch
            # (a row can be poisoned on one architecture and fine on another)
            decoded = self._rows_to_configs([rows[i] for i in retry], problem)
            configs: list = list(rows)
            for i, cfg in zip(retry, decoded):
                configs[i] = cfg
            if broken:
                ex = self._rebuild()
            for a in archs:
                self._evaluate_with_retries(
                    configs, retry, out[a], a, ex, problem, cancel=cancel,
                    attempt_timeout_s=self.job_timeout_s)
        return out

    def _n_chunks(self, n_items: int) -> int:
        if self.mode == "thread":
            return max(1, min(self.workers, n_items // _THREAD_CHUNK_FLOOR))
        return min(self.workers, n_items)

    def _run_chunks(self, items: list, submit, *,
                    cancel: threading.Event | None = None,
                    deadline: float | None = None
                    ) -> tuple[list, list[int], bool]:
        """Fan ``items`` out as worker chunks (``submit(chunk) -> Future``).

        Returns ``(done, retry, broken)``: ``done`` as ``(lo, hi, result)``
        per successful chunk, ``retry`` the item indices of chunks that
        raised (poison isolation runs them one by one), and ``broken`` True
        when the executor must be rebuilt before retrying — after a
        BrokenExecutor, or after the watchdog fired (the hung chunk's
        thread still occupies the old executor).

        ``deadline`` (monotonic) is the batch watchdog: chunks still
        pending then are cancelled and routed to the per-config retry
        path, where each config gets its own attempt timeout.
        ``cancel`` abandons the whole batch by raising
        :class:`EvalCancelled` — the lease-lost fast exit.
        """
        n_chunks = self._n_chunks(len(items))
        bounds = [round(i * len(items) / n_chunks)
                  for i in range(n_chunks + 1)]
        spans = [(bounds[i], bounds[i + 1]) for i in range(n_chunks)
                 if bounds[i] < bounds[i + 1]]
        pending = {submit(items[lo:hi]): (lo, hi) for lo, hi in spans}
        done: list = []
        retry: list[int] = []
        broken = False
        block = cancel is None and deadline is None
        while pending:
            if cancel is not None and cancel.is_set():
                for fut in pending:
                    fut.cancel()
                self.stats["cancelled"] += 1
                raise EvalCancelled("batch abandoned (lease lost)")
            finished, _ = wait(list(pending),
                               timeout=None if block else 0.05,
                               return_when=FIRST_COMPLETED)
            for fut in finished:
                lo, hi = pending.pop(fut)
                try:
                    done.append((lo, hi, fut.result()))
                except BrokenExecutor:
                    retry.extend(range(lo, hi))
                    broken = True
                except Exception:
                    retry.extend(range(lo, hi))  # isolate the poison item(s)
            if deadline is not None and pending \
                    and time.monotonic() >= deadline:
                for fut, (lo, hi) in pending.items():
                    fut.cancel()
                    retry.extend(range(lo, hi))
                pending.clear()
                self.stats["timeouts"] += 1
                broken = True
        return done, retry, broken

    def _evaluate_chunked(self, items: list, arch: str, chunk_fn,
                          to_configs, problem: TunableProblem,
                          cancel: threading.Event | None = None
                          ) -> list[Trial]:
        ex = self._executor()
        deadline = (None if self.job_timeout_s is None
                    else time.monotonic() + self.job_timeout_s)

        # 1. chunked fast path: one evaluate_many per worker
        with span("pool.evaluate", cat="pool", n=len(items), arch=arch,
                  mode=self.mode):
            done, retry, broken = self._run_chunks(
                items, lambda chunk: ex.submit(chunk_fn, problem, chunk,
                                               arch),
                cancel=cancel, deadline=deadline)
        out: list[Trial | None] = [None] * len(items)
        for lo, hi, trials in done:
            out[lo:hi] = trials

        # 2. per-config retry path through the job queue
        if retry:
            configs = items
            if to_configs is not None:       # rows: decode just the retries
                decoded = to_configs([items[i] for i in retry], problem)
                configs = list(items)
                for i, cfg in zip(retry, decoded):
                    configs[i] = cfg
            if broken:
                ex = self._rebuild()
            self._evaluate_with_retries(configs, retry, out, arch, ex,
                                        problem, cancel=cancel,
                                        attempt_timeout_s=self.job_timeout_s)
        return out  # type: ignore[return-value]

    def _evaluate_with_retries(self, configs: list[Config], indices: list[int],
                               out: list, arch: str, ex: Executor,
                               problem: TunableProblem | None = None, *,
                               cancel: threading.Event | None = None,
                               attempt_timeout_s: float | None = None) -> None:
        problem = problem or self.problem
        queue = JobQueue(self.max_retries)
        for i in indices:
            queue.submit(i, configs[i])       # key == batch index: unique

        running: dict = {}
        deadlines: dict = {}

        def launch() -> None:
            nonlocal ex
            while True:
                job = queue.take()
                if job is None:
                    return
                try:
                    fut = ex.submit(_evaluate_one, problem, job.config,
                                    arch)
                except BrokenExecutor:
                    ex = self._rebuild()
                    fut = ex.submit(_evaluate_one, problem, job.config,
                                    arch)
                running[fut] = job
                if attempt_timeout_s is not None:
                    deadlines[fut] = time.monotonic() + attempt_timeout_s

        launch()
        block = cancel is None and attempt_timeout_s is None
        while running:
            if cancel is not None and cancel.is_set():
                for fut in running:
                    fut.cancel()
                self.stats["cancelled"] += 1
                raise EvalCancelled("batch abandoned (lease lost)")
            done, _ = wait(list(running), timeout=None if block else 0.05,
                           return_when=FIRST_COMPLETED)
            for fut in done:
                job = running.pop(fut)
                deadlines.pop(fut, None)
                err = fut.exception()
                if err is None:
                    queue.complete(job, fut.result())
                else:
                    # a BrokenExecutor here also fails innocent in-flight
                    # jobs; their retries run on the rebuilt pool.  Attempts
                    # are counted for everyone so a config that kills its
                    # worker every time still terminates as poisoned.
                    job.timed_out = False
                    queue.fail(job, repr(err))   # requeue or poison
            if attempt_timeout_s is not None and running:
                now = time.monotonic()
                hung = [f for f, dl in deadlines.items()
                        if f in running and dl <= now]
                for fut in hung:
                    job = running.pop(fut)
                    deadlines.pop(fut, None)
                    fut.cancel()
                    # each retry gets a fresh attempt budget; a config
                    # whose every attempt times out poisons with the
                    # timeout marker (see the tail loop below)
                    job.timed_out = True
                    self.stats["timeouts"] += 1
                    queue.fail(job, "evaluation timed out after "
                                    f"{attempt_timeout_s:g}s")
                if hung:
                    # the hung attempts' threads still occupy the old
                    # executor — retries need fresh workers
                    ex = self._rebuild()
            launch()

        for i in indices:
            job = queue.job(i)
            if job is not None and job.state == DONE:
                out[i] = job.result
            else:
                info = {"error": job.error if job else "lost",
                        "poison": True,
                        "attempts": job.attempts if job else 0}
                if job is not None and job.timed_out:
                    info["timeout"] = True
                out[i] = Trial(configs[i], math.inf, arch, valid=False,
                               info=info)


# --------------------------------------------------------------------- #
# broker workers: the detached fleet behind a durable job queue
# --------------------------------------------------------------------- #
class BrokerWorker:
    """One worker loop serving a :class:`~repro.orchestrator.broker.Broker`.

    The fleet member behind ``python -m repro.orchestrator worker``:
    leases one job at a time, keeps the lease alive from a heartbeat
    thread while the evaluation runs, and publishes the result —
    ``complete`` on success, ``fail`` (requeue, attempts-capped) on an
    infrastructure error.  *Evaluation* faults never fail the job: the
    batch runs through this worker's own :class:`WorkerPool`, whose
    per-config retry/poison machinery turns a raising config into an
    invalid trial exactly as in-process evaluation would — so broker
    results are bit-identical to pool results, poison markers included.

    Problems are materialized from the registry by name (the job payload
    carries ``problem``/``pk``) and cached, one live problem + one warm
    pool per problem for the life of the worker: a campaign's stream of
    jobs pays the space compile once, like the in-process scheduler.
    """

    def __init__(self, broker, *, worker_id: str | None = None,
                 workers: int = 2, mode: str = "auto", max_retries: int = 2,
                 lease_s: float = 30.0, poll_s: float = 0.05,
                 job_timeout_s: float | None = None, log=None,
                 clock=time.monotonic):
        from .broker import default_worker_id
        self.broker = broker
        self.worker_id = worker_id or default_worker_id()
        # idle-age bookkeeping measures *durations*, so the monotonic
        # clock is correct (wall-time steps must not retire a worker);
        # injectable so tests drive --max-idle without real sleeping
        self._clock = clock
        self.workers = workers
        self.mode = mode
        self.max_retries = max_retries
        self.lease_s = lease_s
        self.poll_s = poll_s
        self.job_timeout_s = job_timeout_s
        self.log = log or (lambda msg: None)
        self._problems: dict[str, TunableProblem] = {}
        self._pools: dict[str, WorkerPool] = {}

    # -- problem/pool cache ------------------------------------------------ #
    def _problem(self, payload: dict) -> tuple[TunableProblem, WorkerPool]:
        from .registry import make_problem
        key = json.dumps([payload["problem"], payload.get("pk", {})],
                         sort_keys=True)
        if key not in self._problems:
            problem = make_problem(payload["problem"], **payload.get("pk", {}))
            problem.space.compile_eagerly()
            self._problems[key] = problem
            self._pools[key] = WorkerPool(
                problem, payload["archs"][0], workers=self.workers,
                mode=self.mode, max_retries=self.max_retries,
                job_timeout_s=self.job_timeout_s)
        return self._problems[key], self._pools[key]

    # -- evaluation -------------------------------------------------------- #
    def _evaluate(self, payload: dict,
                  cancel: threading.Event | None = None) -> dict:
        from .broker import encode_trial
        problem, pool = self._problem(payload)
        archs = list(payload["archs"])
        if payload.get("rows") is not None:
            rows = [int(r) for r in payload["rows"]]
            if len(archs) > 1:
                per_arch = pool.evaluate_rows(rows, archs=archs,
                                              problem=problem, cancel=cancel)
            else:
                per_arch = {archs[0]: pool.evaluate_rows(
                    rows, arch=archs[0], problem=problem, cancel=cancel)}
        else:
            cfgs = [problem.space.decode(c) for c in payload["configs"]]
            per_arch = {a: pool.evaluate(cfgs, a, problem=problem,
                                         cancel=cancel)
                        for a in archs}
        return {"arch_trials": {a: [encode_trial(t) for t in trials]
                                for a, trials in per_arch.items()}}

    def _pool_stat(self, name: str) -> int:
        return sum(p.stats.get(name, 0) for p in self._pools.values())

    # -- the loop ---------------------------------------------------------- #
    def _heartbeat_loop(self, job_id: int, stop: threading.Event,
                        cancel: threading.Event) -> None:
        # its own broker connection (SQLite connections are thread-local);
        # a False heartbeat means the lease was reaped — this worker was
        # presumed dead and the job re-leased, so stop renewing AND set
        # ``cancel``: our eventual complete/fail would be rejected
        # (concurrent-worker dedup), so finishing the doomed batch is
        # pure waste — the pool abandons it at the next chunk boundary
        interval = max(self.lease_s / 3.0, 0.01)
        while not stop.wait(interval):
            stall = chaos.fire(chaos.WORKER_HEARTBEAT_STALL)
            if stall is not None:
                # injected GC pause / network partition: no renewals for
                # stall_s — past the lease, the broker reaps us
                if stop.wait(float(stall.get("stall_s", self.lease_s))):
                    return
            with span("broker.heartbeat", cat="broker", job=job_id):
                alive = self.broker.heartbeat(job_id, self.worker_id,
                                              self.lease_s)
            if not alive:
                cancel.set()
                return

    def _record_job_metrics(self, result: dict, seconds: float,
                            timeouts: int = 0) -> None:
        """Durable per-job throughput samples into the broker's metrics
        stream.  Always recorded (not gated by the in-process telemetry
        flag): one insert per *job* — a whole evaluation batch — so the
        cost is noise, and the fleet view works without every worker
        opting in.  Recorded before ``complete``, so the samples survive
        even when the lease was lost and the result is rejected — the
        work happened either way."""
        trials = result["arch_trials"]
        evals = sum(len(ts) for ts in trials.values())
        poison = sum(1 for ts in trials.values()
                     for _, _, info in ts if info.get("poison"))
        samples = [
            {"name": "jobs", "value": 1, "kind": "counter"},
            {"name": "evals", "value": evals, "kind": "counter"},
            {"name": "eval_s", "value": seconds, "kind": "counter"},
            {"name": "poison", "value": poison, "kind": "counter"},
            {"name": "configs_per_s", "kind": "gauge",
             "value": evals / seconds if seconds > 0 else 0.0},
        ]
        if timeouts:
            samples.append({"name": "timeouts", "value": timeouts,
                            "kind": "counter"})
        if chaos.active():
            # observed fault schedule, cumulative per worker process:
            # gauges (last-write-wins per worker id) sum across a fleet
            # to the total injected-fault count the bench publishes
            samples.extend({"name": f"chaos.{site}", "kind": "gauge",
                            "value": st["fires"]}
                           for site, st in chaos.stats().items()
                           if st["fires"])
        try:
            self.broker.record_metrics(self.worker_id, samples)
        except Exception as e:    # telemetry must never take down a worker
            self.log(f"job metrics record failed: {e!r}")

    def serve_one(self, job_id: int, payload: dict) -> bool:
        """Evaluate one leased job; returns True if the result landed."""
        stop = threading.Event()
        cancel = threading.Event()
        hb = threading.Thread(target=self._heartbeat_loop,
                              args=(job_id, stop, cancel), daemon=True)
        hb.start()
        t0 = time.monotonic()
        timeouts0 = self._pool_stat("timeouts")
        try:
            with span("worker.job", cat="worker", job=job_id):
                result = self._evaluate(payload, cancel=cancel)
        except EvalCancelled:
            # the heartbeat thread observed a reaped lease: the job was
            # already re-leased elsewhere and our result would be
            # rejected — don't complete, don't fail (that would race the
            # new holder), just record the abandonment and lease again
            try:
                self.broker.record_metrics(self.worker_id, [
                    {"name": "abandoned", "value": 1, "kind": "counter"}])
            except Exception:
                pass
            self.log(f"job {job_id} abandoned (lease lost mid-batch)")
            return False
        except Exception as e:
            # evaluation infrastructure error: requeue the job (attempts-
            # capped).  KeyboardInterrupt/SystemExit propagate instead —
            # the worker dies and the lease expires, which is the same
            # requeue without burning an attempt on an operator Ctrl-C.
            with span("broker.fail", cat="broker", job=job_id):
                self.broker.fail(job_id, self.worker_id, repr(e))
            self.log(f"job {job_id} failed: {e!r}")
            return False
        finally:
            stop.set()
            hb.join()
        chaos.crash(chaos.WORKER_CRASH_BEFORE_COMPLETE)
        self._record_job_metrics(result, time.monotonic() - t0,
                                 timeouts=self._pool_stat("timeouts")
                                 - timeouts0)
        with span("broker.complete", cat="broker", job=job_id):
            ok = self.broker.complete(job_id, self.worker_id, result)
        self.log(f"job {job_id} {'done' if ok else 'lost lease'}")
        return ok

    def run(self, *, max_jobs: int | None = None,
            max_idle_s: float | None = None,
            stop: threading.Event | None = None) -> int:
        """Serve jobs until stopped; returns how many were served.

        ``max_idle_s`` bounds how long the worker polls an empty queue
        before exiting (fleet teardown without a control channel);
        ``max_jobs`` and ``stop`` exist for tests and manual drains.
        """
        served = 0
        idle_since = self._clock()
        while True:
            if stop is not None and stop.is_set():
                break
            if max_jobs is not None and served >= max_jobs:
                break
            with span("broker.lease", cat="broker"):
                leased = self.broker.lease(self.worker_id, self.lease_s)
            if leased is None:
                if (max_idle_s is not None
                        and self._clock() - idle_since > max_idle_s):
                    break
                time.sleep(self.poll_s)
                continue
            self.serve_one(*leased)
            served += 1
            idle_since = self._clock()
        for pool in self._pools.values():
            pool.close()
        return served
