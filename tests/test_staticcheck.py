"""Staticcheck conformance: every lint rule has a positive case (the
violation is caught) and a negative case (the compliant idiom passes),
the suppression/baseline machinery works, the shipped tree lints clean,
and the space auditor flags a deliberately pathological space while
passing every registered kernel space.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core.space import Constraint, Param, SearchSpace
from repro.staticcheck import (Engine, apply_baseline, audit_space,
                               default_rules, load_baseline, write_baseline)

REPO_SRC = Path(__file__).resolve().parent.parent / "src"


def lint(path: str, code: str):
    """Lint one snippet as though it lived at ``path``."""
    eng = Engine(default_rules())
    return eng.lint_source(path, textwrap.dedent(code))


def rule_ids(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------- #
# wall-clock
# --------------------------------------------------------------------- #

def test_wall_clock_flagged_in_deterministic_seam():
    fs = lint("repro/orchestrator/store.py", """
        import time
        def stamp():
            return time.time()
    """)
    assert rule_ids(fs) == ["wall-clock"]
    assert fs[0].line == 4


def test_wall_clock_default_arg_reference_ok():
    # referencing time.time as an injectable default is the sanctioned
    # pattern — only *calling* it inline is a violation
    fs = lint("repro/orchestrator/store.py", """
        import time
        class S:
            def __init__(self, clock=time.time):
                self._clock = clock
            def stamp(self):
                return self._clock()
    """)
    assert fs == []


def test_wall_clock_ignored_outside_seam():
    fs = lint("repro/orchestrator/doctor.py", """
        import time
        def stamp():
            return time.time()
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# global-rng
# --------------------------------------------------------------------- #

def test_global_rng_flagged():
    fs = lint("repro/core/tuners/genetic.py", """
        import random
        import numpy as np
        def draw():
            return random.random() + np.random.rand()
    """)
    assert rule_ids(fs) == ["global-rng", "global-rng"]


def test_instance_rng_ok():
    fs = lint("repro/core/tuners/genetic.py", """
        import random
        import numpy as np
        def draw(seed):
            rng = random.Random(seed)
            g = np.random.default_rng(seed)
            return rng.random() + g.random()
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# chaos-site
# --------------------------------------------------------------------- #

def test_unregistered_chaos_site_flagged():
    fs = lint("repro/orchestrator/workers.py", """
        from . import chaos
        def hook():
            chaos.fire("worker.crash.before_compleat")
    """)
    assert rule_ids(fs) == ["chaos-site"]
    assert "before_compleat" in fs[0].message


def test_registered_chaos_site_and_constant_ok():
    fs = lint("repro/orchestrator/workers.py", """
        from . import chaos
        def hook():
            chaos.fire("eval.hang")
            chaos.crash(chaos.WORKER_CRASH_BEFORE_COMPLETE)
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# telemetry-name
# --------------------------------------------------------------------- #

def test_undocumented_span_flagged():
    fs = lint("repro/orchestrator/runner.py", """
        from ..telemetry.trace import span
        def work():
            with span("session.frobnicate", cat="session"):
                pass
    """)
    assert rule_ids(fs) == ["telemetry-name"]


def test_span_wrong_category_flagged():
    fs = lint("repro/orchestrator/runner.py", """
        from ..telemetry.trace import span
        def work():
            with span("journal.append", cat="broker"):
                pass
    """)
    assert rule_ids(fs) == ["telemetry-name"]
    assert "cat='store'" in fs[0].message


def test_documented_span_and_metric_ok():
    fs = lint("repro/orchestrator/runner.py", """
        from ..telemetry import metrics as _metrics
        from ..telemetry.trace import span
        def work():
            with span("session.ask", cat="session"):
                _metrics.counter("session.evals").inc()
    """)
    assert fs == []


def test_undocumented_metric_flagged():
    fs = lint("repro/orchestrator/runner.py", """
        from ..telemetry import metrics as _metrics
        def work():
            _metrics.counter("session.bogus_counter").inc()
    """)
    assert rule_ids(fs) == ["telemetry-name"]


# --------------------------------------------------------------------- #
# journal-keys
# --------------------------------------------------------------------- #

def test_undocumented_journal_key_flagged():
    fs = lint("repro/orchestrator/store.py", """
        def rec(key, t):
            return {"k": key, "o": t.objective, "v": t.valid, "z": 1}
    """)
    assert rule_ids(fs) == ["journal-keys"]
    assert "'z'" in fs[0].message


def test_documented_journal_record_ok():
    fs = lint("repro/orchestrator/store.py", """
        def rec(key, t, info):
            rec = {"k": key, "o": t.objective, "v": t.valid}
            if info:
                rec["i"] = info
            return rec
    """)
    assert fs == []


def test_non_journal_dicts_ignored():
    # single-char keys that share nothing with the record grammar, and
    # multi-char-key dicts, are not journal records
    fs = lint("repro/orchestrator/store.py", """
        def other():
            return ({"x": 1, "y": 2}, {"kind": "a", "other": "b"})
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# model-store-keys
# --------------------------------------------------------------------- #

def test_undocumented_model_header_field_flagged():
    fs = lint("repro/core/surrogate/store.py", """
        def header(model):
            return {"magic": "repro-models", "version": 1,
                    "problem": model.problem, "extra_field": True}
    """)
    assert rule_ids(fs) == ["model-store-keys"]
    assert "extra_field" in fs[0].message


def test_documented_model_header_ok():
    fs = lint("repro/core/surrogate/store.py", """
        def header(model, checksum):
            return {"magic": "repro-models", "version": 1,
                    "problem": model.problem, "created_at": 0.0,
                    "feature_names": [], "archs": [], "params": {},
                    "n_rows": 0, "sections": {"model": checksum}}
    """)
    assert fs == []


def test_non_header_dicts_and_other_files_ignored():
    # dicts without a "magic" key are not headers; header-shaped dicts
    # outside the surrogate store module are someone else's schema
    fs = lint("repro/core/surrogate/store.py", """
        def other():
            return {"problem": "gemm", "anything": 1}
    """)
    assert fs == []
    fs = lint("repro/servedb/snapshot.py", """
        def header():
            return {"magic": "other-format", "custom": 1}
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# lookup-raise
# --------------------------------------------------------------------- #

def test_raise_in_public_lookup_flagged():
    fs = lint("repro/servedb/lookup.py", """
        class ServeDB:
            def lookup(self, kernel):
                if kernel is None:
                    raise ValueError("no kernel")
    """)
    assert rule_ids(fs) == ["lookup-raise"]
    assert "lookup" in fs[0].message


def test_raise_in_private_helper_ok():
    fs = lint("repro/servedb/lookup.py", """
        class ServeDB:
            def _check(self, kernel):
                raise ValueError("internal")
    """)
    assert fs == []


def test_raise_elsewhere_ok():
    fs = lint("repro/servedb/snapshot.py", """
        def publish(snap):
            raise IOError("disk full")
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# broker-tx
# --------------------------------------------------------------------- #

def test_mutation_outside_tx_flagged():
    fs = lint("repro/orchestrator/broker.py", """
        class SQLiteBroker:
            def submit(self, payload):
                self._conn().execute(
                    "INSERT INTO jobs (payload) VALUES (?)", (payload,))
    """)
    assert rule_ids(fs) == ["broker-tx"]
    assert "INSERT" in fs[0].message


def test_mutation_inside_tx_ok():
    fs = lint("repro/orchestrator/broker.py", """
        class SQLiteBroker:
            def submit(self, payload):
                with self._tx() as cur:
                    cur.execute(
                        "INSERT INTO jobs (payload) VALUES (?)", (payload,))
            def _reap_cur(self, cur):
                cur.execute("UPDATE jobs SET state=? WHERE id=?", (1, 2))
            def counts(self):
                return self._conn().execute(
                    "SELECT state, COUNT(*) FROM jobs").fetchall()
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# retry-sleep
# --------------------------------------------------------------------- #

def test_sleep_in_except_handler_flagged():
    fs = lint("repro/orchestrator/anything.py", """
        import time
        def fetch(conn):
            for attempt in range(5):
                try:
                    return conn.get()
                except OSError:
                    time.sleep(2 ** attempt)
    """)
    assert rule_ids(fs) == ["retry-sleep"]


def test_idle_polling_sleep_ok():
    fs = lint("repro/orchestrator/anything.py", """
        import time
        def poll(queue):
            while queue.empty():
                time.sleep(0.5)
    """)
    assert fs == []


# --------------------------------------------------------------------- #
# engine machinery: suppressions, baselines, parse errors
# --------------------------------------------------------------------- #

def test_same_line_suppression():
    fs = lint("repro/orchestrator/store.py", """
        import time
        def stamp():
            return time.time()  # repro-lint: disable=wall-clock
    """)
    assert fs == []


def test_comment_line_suppresses_next_line():
    fs = lint("repro/orchestrator/store.py", """
        import time
        def stamp():
            # repro-lint: disable=wall-clock
            return time.time()
    """)
    assert fs == []


def test_suppression_is_rule_specific():
    fs = lint("repro/orchestrator/store.py", """
        import time
        def stamp():
            return time.time()  # repro-lint: disable=global-rng
    """)
    assert rule_ids(fs) == ["wall-clock"]


def test_baseline_roundtrip(tmp_path):
    code = """
        import time
        def stamp():
            return time.time()
    """
    findings = lint("repro/orchestrator/store.py", code)
    assert findings
    bl = tmp_path / "baseline.json"
    write_baseline(bl, findings)
    assert apply_baseline(findings, load_baseline(bl)) == []
    # a new, different finding is NOT excused by the old baseline
    fresh = lint("repro/orchestrator/store.py", """
        import random
        def draw():
            return random.random()
    """)
    assert rule_ids(apply_baseline(fresh, load_baseline(bl))) == ["global-rng"]


def test_baseline_key_survives_line_shifts():
    a = lint("repro/orchestrator/store.py", """
        import time
        def stamp():
            return time.time()
    """)
    b = lint("repro/orchestrator/store.py", """
        import time
        # an unrelated comment pushing the violation down
        def stamp():
            return time.time()
    """)
    assert a[0].line != b[0].line
    assert a[0].baseline_key == b[0].baseline_key


def test_syntax_error_is_a_finding_not_a_crash():
    fs = lint("repro/orchestrator/store.py", "def broken(:\n")
    assert rule_ids(fs) == ["parse-error"]


def test_shipped_tree_lints_clean():
    eng = Engine(default_rules(), root=REPO_SRC)
    findings = eng.lint_paths([REPO_SRC / "repro"])
    assert findings == [], "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# space auditor
# --------------------------------------------------------------------- #

def _pathological_space() -> SearchSpace:
    """Two 0..4 params constrained to two opposite corners: value 2 of
    each param is dead, and the two corners are Hamming-1 disconnected."""
    return SearchSpace(
        [Param("x", tuple(range(5))), Param("y", tuple(range(5)))],
        [Constraint(
            "corners",
            lambda c: (c["x"] <= 1 and c["y"] <= 1)
            or (c["x"] >= 3 and c["y"] >= 3),
            vec=lambda cols: ((cols["x"] <= 1) & (cols["y"] <= 1))
            | ((cols["x"] >= 3) & (cols["y"] >= 3)))],
        name="pathological")


def test_audit_flags_pathological_space():
    rep = audit_space(_pathological_space())
    assert not rep.ok
    checks = {f.check for f in rep.findings}
    assert "dead-value" in checks
    assert "disconnected" in checks
    assert rep.n_components == 2
    assert rep.n_valid == 8
    dead = [f for f in rep.findings if f.check == "dead-value"]
    assert len(dead) == 2            # both x and y have value 2 dead


def test_audit_unsatisfiable_space():
    sp = SearchSpace(
        [Param("x", (0, 1))],
        [Constraint("never", lambda c: False,
                    vec=lambda cols: cols["x"] < 0)],
        name="empty")
    rep = audit_space(sp)
    assert not rep.ok
    assert [f.check for f in rep.findings] == ["unsatisfiable"]
    assert rep.findings[0].severity == "error"


def test_audit_redundant_constraint_is_info_only():
    # x<=y keeps every value of both params alive (x=v pairs with y=3,
    # y=v pairs with x=0) and the staircase stays Hamming-1 connected,
    # so the only finding is the implied x<=y+1
    sp = SearchSpace(
        [Param("x", tuple(range(4))), Param("y", tuple(range(4)))],
        [Constraint("x_le_y", lambda c: c["x"] <= c["y"],
                    vec=lambda cols: cols["x"] <= cols["y"]),
         Constraint("x_le_y1", lambda c: c["x"] <= c["y"] + 1,  # implied
                    vec=lambda cols: cols["x"] <= cols["y"] + 1)],
        name="redundant")
    rep = audit_space(sp)
    red = [f for f in rep.findings if f.check == "redundant-constraint"]
    assert len(red) == 1 and "x_le_y1" in red[0].message
    assert red[0].severity == "info"
    assert rep.ok                     # hygiene, not a failure


def test_audit_clean_space_ok():
    sp = SearchSpace([Param("x", (0, 1, 2))], name="clean")
    rep = audit_space(sp)
    assert rep.ok and rep.findings == [] and rep.n_components == 1


@pytest.mark.parametrize("name", [
    "gemm", "conv2d", "pnpoly", "nbody", "hotspot", "dedisp", "expdist",
    "attention", "mla_decode", "toy_quad", "toy_rastrigin"])
def test_all_shipped_spaces_pass_audit(name):
    from repro.orchestrator.registry import make_problem
    rep = audit_space(make_problem(name).space)
    bad = [f.render() for f in rep.findings if f.severity != "info"]
    assert rep.ok, f"{name}: " + "; ".join(bad)
    assert rep.n_components == 1, \
        f"{name}: valid region disconnected ({rep.n_components} components)"


# --------------------------------------------------------------------- #
# CLI surface
# --------------------------------------------------------------------- #

def _run_cli(*argv):
    import os
    env = dict(os.environ, PYTHONPATH=str(REPO_SRC))
    return subprocess.run(
        [sys.executable, "-m", "repro.orchestrator", *argv],
        capture_output=True, text=True, env=env)


def test_cli_lint_strict_clean_tree_exits_zero():
    r = _run_cli("lint", "--strict", str(REPO_SRC / "repro"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout


def test_cli_lint_strict_fails_on_violation(tmp_path):
    bad = tmp_path / "repro" / "orchestrator" / "store.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import time\n\ndef stamp():\n    return time.time()\n")
    r = _run_cli("lint", "--strict", str(bad))
    assert r.returncode == 1
    assert "wall-clock" in r.stdout
    # advisory mode reports but exits 0
    r = _run_cli("lint", str(bad))
    assert r.returncode == 0 and "wall-clock" in r.stdout
    # a written baseline excuses it for strict mode
    bl = tmp_path / "baseline.json"
    r = _run_cli("lint", "--write-baseline", str(bl), str(bad))
    assert r.returncode == 0 and bl.exists()
    r = _run_cli("lint", "--strict", "--baseline", str(bl), str(bad))
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_lint_json_output():
    r = _run_cli("lint", "--json", str(REPO_SRC / "repro" / "staticcheck"))
    assert r.returncode == 0
    rec = json.loads(r.stdout)
    assert rec["ok"] is True and rec["findings"] == []


def test_doctor_lint_flag(tmp_path):
    from repro.orchestrator.doctor import diagnose
    from repro.orchestrator.store import SessionStore
    store = SessionStore(tmp_path / "sessions")
    report = diagnose(store, lint=True)
    assert report["lint"] == {"findings": []}
    assert report["ok"]
