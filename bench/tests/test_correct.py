"""The comparison that decides `correct` fails the control and each fault
a cell can have, at tiny shapes on the CPU.  The readings the limits were
set from were taken on the chip at the cells' own sizes (PERF.md)."""

import pytest

from conftest import run_tiny
from harness.catalog import Catalog


@pytest.mark.parametrize("config", ["dsc33b-attn8k", "dsc33b-up2k"])
def test_control_fails_and_program_passes(tiny, config):
    from control import readings

    cat = Catalog(tiny / "bench")
    limits = cat.limits(f"{config}.deploy")
    for r in readings(cat, config, (1, 2, 3), rehearse=True):
        assert r["program"]["rel_l2"] <= limits["rel_l2"]
        assert r["program"]["max_err"] <= limits["max_err"]
        assert (r["control"]["rel_l2"] > limits["rel_l2"]
                or r["control"]["max_err"] > limits["max_err"])


def _alter_one_row(y):
    """A token's (or a row's) answer altered where it is produced."""
    return y.at[..., 3, :].set(0) if y.ndim == 3 else y.at[3, :].set(0)


@pytest.mark.parametrize("cell", ["dsc33b-attn8k.deploy",
                                  "dsc33b-up2k.deploy"])
def test_deploy_altered_answer_is_not_correct(tiny, interpret, monkeypatch,
                                              cell):
    cat = Catalog(tiny / "bench")
    adapter = cat.kernel(cat.config(cell.split(".")[0])["kernel"])
    deploy = adapter.deploy

    def broken(x, shape, *, interpret=False):
        call = deploy(x, shape, interpret=interpret)
        return lambda: _alter_one_row(call())

    monkeypatch.setattr(adapter, "deploy", broken)
    r = run_tiny(tiny, cell, catalog=cat)
    assert r["correct"] is False
    assert r["checks"]["max_err"]["value"] > r["checks"]["max_err"]["limit"]


def test_campaign_altered_answer_is_not_correct(tiny, interpret,
                                                monkeypatch):
    from repro.kernels.common import KernelProblem

    compile_kernel = KernelProblem.compile_kernel

    def broken(self, config, inputs):
        run = compile_kernel(self, config, inputs)
        return lambda: _alter_one_row(run())

    monkeypatch.setattr(KernelProblem, "compile_kernel", broken)
    r = run_tiny(tiny, "dsc33b-attn8k.campaign")
    assert r["correct"] is False
    assert r["failed"] > 0


def test_campaign_lost_journal_line_is_not_correct(tiny, interpret,
                                                   monkeypatch):
    from repro.orchestrator.store import SessionStore

    append = SessionStore.append_trials

    def drop_first(self, sid, space, trials):
        return append(self, sid, space, list(trials)[1:])

    monkeypatch.setattr(SessionStore, "append_trials", drop_first)
    r = run_tiny(tiny, "dsc33b-attn8k.campaign")
    assert r["correct"] is False
    assert r["checks"]["journal_lost"]["value"] > 0


def test_campaign_refusal_without_its_error_is_not_correct(tiny, interpret,
                                                           monkeypatch):
    import math

    from repro.core.problem import MeasuredProblem, Trial

    evaluate = MeasuredProblem.evaluate
    seen = []

    def first_refused_silently(self, config, arch="v5e"):
        seen.append(config)
        if len(seen) == 2:          # the window's first; the warm-up's is 1
            return Trial(config, math.inf, arch, valid=False)
        return evaluate(self, config, arch)

    monkeypatch.setattr(MeasuredProblem, "evaluate", first_refused_silently)
    r = run_tiny(tiny, "dsc33b-attn8k.campaign")
    assert r["correct"] is False
    assert r["checks"]["refused_silent"]["value"] == 1
    assert r["checks"]["rel_l2"]["value"] <= r["checks"]["rel_l2"]["limit"]
