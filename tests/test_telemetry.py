"""Telemetry layer: span tracing, fleet metrics, live views.

The load-bearing property sits in the middle of the file: trajectories
AND journal bytes are bit-identical with telemetry on vs off.  The
observability layer reads the tuning loop; it must never steer it.
"""

from __future__ import annotations

import json
import math
import threading
import time

import pytest

from repro import telemetry
from repro.telemetry import metrics as tmetrics
from repro.telemetry import trace as ttrace
from repro.telemetry.trace import span, traced, tracing


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts and ends with clean, disabled telemetry."""
    telemetry.disable()
    ttrace.clear()
    tmetrics.reset()
    yield
    telemetry.disable()
    ttrace.clear()
    tmetrics.reset()


# --------------------------------------------------------------------- #
# span tracing
# --------------------------------------------------------------------- #
def test_spans_nest_and_record_depth():
    ttrace.enable()
    with span("outer", cat="t"):
        with span("inner", cat="t", n=3):
            pass
    evts = ttrace.events()
    # children close (and record) before parents
    assert [e["name"] for e in evts] == ["inner", "outer"]
    by = {e["name"]: e for e in evts}
    assert by["outer"]["depth"] == 0 and by["inner"]["depth"] == 1
    assert by["inner"]["args"] == {"n": 3}
    assert by["inner"]["dur"] <= by["outer"]["dur"]
    # timestamps are µs relative to the enable() origin
    assert by["outer"]["ts"] >= 0.0


def test_ring_buffer_keeps_newest():
    ttrace.enable(buffer=16)
    for i in range(50):
        with span(f"s{i}", cat="t"):
            pass
    evts = ttrace.events()
    assert len(evts) == 16
    assert [e["name"] for e in evts] == [f"s{i}" for i in range(34, 50)]


def test_disabled_span_is_shared_noop():
    assert not ttrace.is_enabled()
    s1 = span("a", cat="t")
    s2 = span("b", cat="t", n=1)
    assert s1 is s2                        # one shared null object, no alloc
    with s1:
        pass
    assert ttrace.events() == []


def _host_events(logdir) -> list[tuple]:
    """(name, start ns, end ns, arguments) of the events on the host
    planes of the profiler trace under ``logdir``."""
    from pathlib import Path

    from jax.profiler import ProfileData

    path = sorted(Path(logdir).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_enabled_spans_land_in_the_profiler_trace(tmp_path):
    import jax

    ttrace.enable()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.window"):
            with span("kernel.build", cat="kernel", key="a=1/b=bf16"):
                with span("kernel.lower", cat="kernel"):
                    time.sleep(0.001)
    got = {n: (a, b, args) for n, a, b, args in _host_events(tmp_path)}
    window, build, lower = (got[n] for n in
                            ("test.window", "kernel.build", "kernel.lower"))
    # nested as they ran, inside the surrounding annotation
    assert window[0] <= build[0] <= lower[0]
    assert lower[1] <= build[1] <= window[1]
    assert build[2]["key"] == "a=1/b=bf16"
    # the ring buffer keeps them as before
    assert [(e["name"], e["depth"]) for e in ttrace.events()] == \
        [("kernel.lower", 1), ("kernel.build", 0)]


def test_disabled_span_writes_no_annotation(tmp_path):
    import jax

    assert not ttrace.is_enabled()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.window"):
            s = span("kernel.build", cat="kernel", key="a=1")
            with s:
                time.sleep(0.001)
    assert s is ttrace._NULL_SPAN
    names = {n for n, *_ in _host_events(tmp_path)}
    assert "test.window" in names and "kernel.build" not in names


def test_disabled_span_imports_nothing():
    import os
    import subprocess
    import sys
    from pathlib import Path

    code = ("import sys\n"
            "from repro.telemetry.trace import span, _NULL_SPAN\n"
            "before = set(sys.modules)\n"
            "with span('kernel.build', cat='kernel', key='a=1') as s:\n"
            "    pass\n"
            "print(s is _NULL_SPAN, sorted(set(sys.modules) - before),"
            " 'jax' in sys.modules)\n")
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src))
    env.pop("REPRO_TRACE", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True)
    assert out.stdout.split() == ["True", "[]", "False"]


def test_spans_without_jax_stay_in_the_ring_buffer(monkeypatch):
    import sys

    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # import fails
    monkeypatch.setattr(ttrace, "_TRACER", ttrace._Tracer())
    ttrace.enable()
    with span("kernel.build", cat="kernel", key="a=1"):
        pass
    assert ttrace._TRACER.annotate is None
    assert [e["name"] for e in ttrace.events()] == ["kernel.build"]


def test_traced_decorator_and_error_capture():
    ttrace.enable()

    @traced("work.step", cat="t")
    def step(x):
        return x + 1

    assert step(1) == 2

    with pytest.raises(ValueError):
        with span("boom", cat="t"):
            raise ValueError("nope")
    evts = {e["name"]: e for e in ttrace.events()}
    assert "work.step" in evts
    assert "ValueError" in evts["boom"]["args"]["error"]


def test_tracing_context_manager_restores_state():
    assert not ttrace.is_enabled()
    with tracing():
        assert ttrace.is_enabled()
        with span("inside", cat="t"):
            pass
        assert len(ttrace.events()) == 1
    assert not ttrace.is_enabled()


def test_thread_local_nesting():
    ttrace.enable()
    seen = []

    def worker():
        with span("child-thread", cat="t"):
            time.sleep(0.005)
        seen.append(True)

    with span("main-thread", cat="t"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    by = {e["name"]: e for e in ttrace.events()}
    # each thread nests independently: both are roots on their own stack
    assert by["main-thread"]["depth"] == 0
    assert by["child-thread"]["depth"] == 0
    assert by["main-thread"]["tid"] != by["child-thread"]["tid"]


def test_exports(tmp_path):
    ttrace.enable()
    with span("alpha", cat="t", n=1):
        with span("beta", cat="t"):
            pass
    jl = ttrace.export_jsonl(tmp_path / "t.jsonl")
    lines = jl.read_text().splitlines()
    header = json.loads(lines[0])
    assert header["trace"] == "repro.telemetry" and header["unit"] == "us"
    recs = [json.loads(x) for x in lines[1:]]
    assert {r["name"] for r in recs} == {"alpha", "beta"}
    ch = ttrace.export_chrome(tmp_path / "t.json")
    data = json.loads(ch.read_text())
    assert data["displayTimeUnit"] == "ms"
    assert len(data["traceEvents"]) == 2
    for e in data["traceEvents"]:
        assert e["ph"] == "X"
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= e.keys()


def test_summarize_orders_by_total():
    ttrace.enable()
    for _ in range(3):
        with span("quick", cat="t"):
            pass
    with span("slow", cat="t"):
        time.sleep(0.02)
    rows = ttrace.summarize(top=2)
    assert rows[0]["name"] == "slow"
    assert rows[1]["name"] == "quick" and rows[1]["count"] == 3
    assert rows[0]["total_ms"] >= rows[0]["max_ms"] >= rows[0]["mean_ms"]


# --------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------- #
def test_metrics_instruments_and_labels():
    tmetrics.enable()
    tmetrics.counter("evals", session="a").inc(5)
    tmetrics.counter("evals", session="a").inc(2)
    tmetrics.counter("evals", session="b").inc()
    tmetrics.gauge("best", session="a").set(3.5)
    tmetrics.gauge("best", session="a").set(1.5)       # last write wins
    h = tmetrics.histogram("lat")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = {(s["name"], tuple(sorted(s["labels"].items()))): s
            for s in tmetrics.snapshot()}
    assert snap[("evals", (("session", "a"),))]["value"] == 7
    assert snap[("evals", (("session", "b"),))]["value"] == 1
    assert snap[("best", (("session", "a"),))]["value"] == 1.5
    hist = snap[("lat", ())]
    assert hist["count"] == 3 and hist["mean"] == pytest.approx(2.0)
    assert hist["min"] == 1.0 and hist["max"] == 3.0


def test_metrics_kind_mismatch_raises():
    tmetrics.enable()
    tmetrics.counter("x")
    with pytest.raises(TypeError):
        tmetrics.gauge("x")


def test_metrics_disabled_is_shared_noop():
    assert not tmetrics.is_enabled()
    a = tmetrics.counter("x", k="v")
    b = tmetrics.gauge("y")
    assert a is b                          # the one shared null instrument
    a.inc(10)
    b.set(5)
    tmetrics.enable()
    assert tmetrics.snapshot() == []       # nothing leaked through


def test_aggregate_samples():
    samples = [
        {"worker": "w1", "name": "evals", "value": 4, "kind": "counter"},
        {"worker": "w1", "name": "evals", "value": 6, "kind": "counter"},
        {"worker": "w1", "name": "rate", "value": 9.0, "kind": "gauge"},
        {"worker": "w1", "name": "rate", "value": 5.0, "kind": "gauge"},
        {"worker": "w2", "name": "evals", "value": 1, "kind": "counter"},
    ]
    agg = tmetrics.aggregate_samples(samples)
    assert agg == {"w1": {"evals": 10.0, "rate": 5.0},
                   "w2": {"evals": 1.0}}


def test_fleet_snapshot_from_memory_broker():
    from repro.orchestrator import MemoryBroker
    from repro.orchestrator.queue import LEASED, PENDING

    b = MemoryBroker()
    b.submit({"problem": "toy_quad", "archs": ["v5e"], "rows": [1],
              "sessions": []})
    b.submit({"problem": "toy_quad", "archs": ["v5e"], "rows": [2],
              "sessions": []})
    b.lease("w-ok", lease_s=30.0)
    b.record_metrics("w-ok", [
        {"name": "evals", "value": 10, "kind": "counter"},
        {"name": "eval_s", "value": 2.0, "kind": "counter"}])
    snap = tmetrics.fleet_snapshot(b)
    assert snap["queue"][PENDING] == 1 and snap["queue"][LEASED] == 1
    w = snap["workers"]["w-ok"]
    assert w["leases"] == 1 and w["stale"] is False
    assert w["heartbeat_age"] >= 0.0
    assert w["evals"] == 10.0
    # derived when the worker never set the gauge: evals / eval_s
    assert w["configs_per_s"] == pytest.approx(5.0)
    # a pure read: nothing was reaped or requeued
    assert b.counts()[LEASED] == 1


def test_memory_broker_jsonl_sink(tmp_path):
    from repro.orchestrator import MemoryBroker

    sink = tmp_path / "metrics.jsonl"
    b = MemoryBroker(metrics_sink=sink)
    b.record_metrics("w1", [{"name": "jobs", "value": 1,
                             "kind": "counter"}])
    b.record_metrics("w1", [{"name": "jobs", "value": 1,
                             "kind": "counter"}])
    recs = [json.loads(x) for x in sink.read_text().splitlines()]
    assert len(recs) == 2
    assert all(r["worker"] == "w1" and r["name"] == "jobs" for r in recs)
    assert recs[0]["ts"] <= recs[1]["ts"]


# --------------------------------------------------------------------- #
# the invariant: telemetry reads the loop, never steers it
# --------------------------------------------------------------------- #
def test_trajectory_and_journal_bit_identical_on_vs_off(tmp_path):
    from repro.orchestrator import SessionSpec, SessionStore, run_session

    spec = SessionSpec(problem="toy_rastrigin", tuner="genetic", budget=48,
                       seed=11, workers=2)

    def run(tag, on):
        store = SessionStore(tmp_path / tag)
        if on:
            telemetry.enable()
        else:
            telemetry.disable()
        res = run_session(spec, store=store)
        telemetry.disable()
        return (res, store._journal_path(spec.session_id).read_bytes())

    res_off, j_off = run("off", on=False)
    res_on, j_on = run("on", on=True)
    assert [t.config for t in res_off.trials] == \
           [t.config for t in res_on.trials]
    assert [t.objective for t in res_off.trials] == \
           [t.objective for t in res_on.trials]
    assert j_off == j_on


def test_session_spans_and_metrics_land():
    from repro.orchestrator import SessionSpec, run_session

    telemetry.enable()
    spec = SessionSpec(problem="toy_quad", tuner="random", budget=12,
                       seed=0, workers=2)
    res = run_session(spec)
    names = {e["name"] for e in ttrace.events()}
    assert {"session.ask", "session.tell", "pool.evaluate",
            "pool.chunk"} <= names
    snap = {(s["name"], dict(s["labels"]).get("session")): s["value"]
            for s in tmetrics.snapshot()}
    sid = spec.session_id
    assert snap[("session.evals", sid)] == len(res.trials)


def test_measured_problem_records_build_measure_split():
    from repro.core.problem import MeasuredProblem
    from repro.core.space import Param, SearchSpace

    space = SearchSpace([Param("a", (1, 2))], name="m")
    prob = MeasuredProblem(space, build=lambda cfg: (lambda: None),
                           repeats=2, warmup=0)
    ttrace.enable()
    t = prob.evaluate({"a": 1}, arch="cpu")
    assert t.valid
    by = {e["name"]: e for e in ttrace.events()}
    assert by["kernel.build"]["cat"] == "kernel"
    assert by["kernel.measure"]["args"]["repeats"] == 2
    # one key ties the config's spans; measure says what it timed
    assert by["kernel.build"]["args"]["key"] == "a=1"
    assert by["kernel.measure"]["args"]["key"] == "a=1"
    assert by["kernel.measure"]["args"]["calls"] == 2
    assert by["kernel.measure"]["args"]["best_s"] == t.objective


# --------------------------------------------------------------------- #
# live views (CLI)
# --------------------------------------------------------------------- #
def _make_session(tmp_path):
    from repro.orchestrator import SessionSpec, SessionStore, run_session

    store = SessionStore(tmp_path / "store")
    spec = SessionSpec(problem="toy_quad", tuner="random", budget=12,
                       seed=0, workers=2)
    run_session(spec, store=store)
    return store, spec.session_id


def test_cli_status_json(tmp_path, capsys):
    from repro.orchestrator.cli import main as cli_main

    store, sid = _make_session(tmp_path)
    rc = cli_main(["status", "--store", str(store.root), "--json"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    rows = [json.loads(x) for x in lines]
    assert len(rows) == 1
    row = rows[0]
    assert row["session"] == sid and row["status"] == "done"
    assert row["evaluated"] == 12 and row["budget"] == 12
    assert isinstance(row["best"], float) and math.isfinite(row["best"])


def test_cli_status_watch_renders_frames(tmp_path, capsys):
    from repro.orchestrator.cli import main as cli_main

    store, sid = _make_session(tmp_path)
    rc = cli_main(["status", "--store", str(store.root), "--watch",
                   "--count", "2", "--interval", "0.01"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("\x1b[2J") == 2               # one clear per frame
    assert sid in out
    assert "[" in out and "12/12" in out           # progress bar
    assert any(c in out for c in "▁▂▃▄▅▆▇█")       # best-so-far sparkline


def test_cli_metrics_dump_and_raw(tmp_path, capsys):
    from repro.orchestrator import SQLiteBroker
    from repro.orchestrator.cli import main as cli_main

    db = tmp_path / "queue.db"
    b = SQLiteBroker(db)
    b.record_metrics("w1", [
        {"name": "jobs", "value": 2, "kind": "counter"},
        {"name": "evals", "value": 40, "kind": "counter"},
        {"name": "eval_s", "value": 4.0, "kind": "counter"}])
    b.close()

    rc = cli_main(["metrics", "--broker", str(db)])
    assert rc == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["workers"]["w1"]["evals"] == 40.0
    assert snap["workers"]["w1"]["configs_per_s"] == pytest.approx(10.0)
    assert "queue" in snap

    rc = cli_main(["metrics", "--broker", str(db), "--raw"])
    assert rc == 0
    recs = [json.loads(x)
            for x in capsys.readouterr().out.strip().splitlines()]
    assert {r["name"] for r in recs} == {"jobs", "evals", "eval_s"}


def test_cli_metrics_refuses_missing_db(tmp_path, capsys):
    from repro.orchestrator.cli import main as cli_main

    missing = tmp_path / "nope" / "queue.db"
    rc = cli_main(["metrics", "--broker", str(missing)])
    assert rc == 2
    assert "no broker db" in capsys.readouterr().err
    assert not missing.exists()


def test_fmt_age_humanizes():
    from repro.orchestrator.cli import _fmt_age

    assert _fmt_age(3.21) == "3.2s"
    assert _fmt_age(0.0) == "0.0s"
    assert _fmt_age(245) == "4.1m"
    assert _fmt_age(9000) == "2.5h"


def test_cli_trace_flag_exports_chrome(tmp_path, capsys):
    from repro.orchestrator.cli import main as cli_main

    out = tmp_path / "trace.json"
    rc = cli_main(["submit", "--problem", "toy_quad", "--tuner", "random",
                   "--budget", "8", "--store", str(tmp_path / "store"),
                   "--trace", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    names = {e["name"] for e in data["traceEvents"]}
    assert {"session.ask", "session.tell"} <= names
    assert not ttrace.is_enabled()         # the flag's enable was scoped
