"""The trace reduction, checked on a small trace recorded on a TPU v5e:
three calls of the deployed GEMM of dsc33b-up2k inside a bench.window
annotation."""

from pathlib import Path

import pytest

from harness import xtrace
from harness.readers import union_s

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" / \
    "gemm3.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return xtrace.reduce(FIXTURE)


def test_busy_lies_inside_the_window(reduced):
    assert reduced.devices == 1
    assert 0 < reduced.busy_s <= reduced.window_s


def test_kernel_time_found_by_name(reduced):
    seconds, matched = reduced.op_seconds(("gemm",))
    assert matched >= 1
    # three calls of a kernel of a few milliseconds each
    assert 3 * 2e-3 < seconds <= reduced.busy_s + 1e-9


def test_breakdown_has_the_contract_shape(reduced):
    b = reduced.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert len(b[key]) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in b[key])
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    gaps = [s for _, s in reduced.gaps]
    idle = reduced.window_s - reduced.busy_s
    assert sum(gaps) == pytest.approx(idle, rel=1e-6, abs=1e-9)


def test_union_of_overlapping_spans():
    spans = [{"name": "a", "ts": 0.0, "dur": 2e6},
             {"name": "b", "ts": 1e6, "dur": 2e6},
             {"name": "a", "ts": 5e6, "dur": 1e6},
             {"name": "c", "ts": 0.0, "dur": 9e6}]
    assert union_s(spans, ("a", "b")) == pytest.approx(4.0)
