"""Each traffic kind driven through the harness's own code, at tiny shapes
in interpret mode on the CPU, and the command's refusal off the TPU."""

import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, run_tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.mark.parametrize("cell", ["dsc33b-up2k.deploy",
                                  "dsc33b-attn8k.deploy",
                                  "dsc33b-attn8k.campaign"])
def test_cell_end_to_end_line(tiny, interpret, cell):
    r = run_tiny(tiny, cell)
    assert KEYS <= set(r)
    assert list(r)[-2] == "checks"            # last key before _stderr
    assert r["correct"] is True, r["_stderr"]
    assert r["attempted"] > 0 and r["failed"] == 0
    rate = "kernel_ms" if cell.endswith(".deploy") else \
        "measured_configs_per_s"
    assert set(r["metrics"]) == {rate, "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 1
    assert "memory_peak_bytes" in r["device"]
    # the numbers compared end standard error, each beside its limit
    tail = r["_stderr"].strip().splitlines()[-len(r["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)


@pytest.mark.parametrize("cell,names", [
    ("dsc33b-attn8k.campaign", {"session_share", "build_s_per_config",
                                "refused_share", "measure_share"}),
    ("dsc33b-up2k.deploy", set())])
def test_traced_run_reports_span_metrics(tiny, interpret, cell, names):
    # off the TPU the trace holds no device plane: the device metrics are
    # left out, never reported as 0
    r = run_tiny(tiny, cell, trace=True)
    assert set(r["metrics"]) == names
    assert "busy_s" not in r["device"]


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "dsc33b-up2k.deploy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
