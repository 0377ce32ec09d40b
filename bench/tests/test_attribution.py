"""The attribution of a campaign's idle device time to the program's spans,
checked on hand-made intervals and on a small trace recorded on a TPU
v5e."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import attribution, xtrace
from harness.attribution import Attribution, idle_by_layer

# one window of 100 ns; the device busy at 10-20, 50-60 and 90-95
WINDOW = (0.0, 100.0)
OPS = [(10.0, 20.0), (50.0, 60.0), (90.0, 95.0)]
SPANS = [
    ("bench.session", 0.0, 100.0),       # the harness's, not the program's
    ("session.ask", 0.0, 8.0),
    ("pool.evaluate", 20.0, 88.0),
    ("kernel.build", 22.0, 40.0),
    ("kernel.lower", 22.0, 30.0),
    ("kernel.compile", 30.0, 40.0),
    ("kernel.measure", 40.0, 70.0),
    ("journal.append", 72.0, 80.0),
    ("PjitFunction(step)", 60.0, 100.0),  # not a program span
]


def test_idle_goes_to_the_innermost_open_span():
    got = idle_by_layer(WINDOW, OPS, SPANS)
    # 0-8 ask, 20-22 pool.evaluate, 70-88 pool.evaluate and journal.append
    assert got["session"] == pytest.approx(8 + 2 + 18)
    # 22-40: kernel.lower then kernel.compile, both inside kernel.build
    assert got["compile"] == pytest.approx(18)
    # 40-50 and 60-70: the gaps on either side of the measured call
    assert got["measure"] == pytest.approx(20)
    # 8-10, 88-90 and 95-100 lie under no program span
    assert got["unspanned"] == pytest.approx(2 + 2 + 5)


def test_layers_partition_the_idle_time():
    got = idle_by_layer(WINDOW, OPS, SPANS)
    busy = sum(b - a for a, b in OPS)
    assert sum(got.values()) == pytest.approx(WINDOW[1] - WINDOW[0] - busy)


@pytest.mark.parametrize("spans,want", [
    ([], {"unspanned": 80}),
    ([("bench.window", 0.0, 100.0)], {"unspanned": 80}),
    ([("kernel.build", -5.0, 200.0)], {"compile": 80}),
    # the span that started last is the innermost
    ([("pool.evaluate", 0.0, 100.0), ("kernel.measure", 1.0, 99.0)],
     {"measure": 78, "session": 2}),
])
def test_gaps_around_one_operation(spans, want):
    got = idle_by_layer(WINDOW, [(40.0, 60.0)], spans)
    assert got == pytest.approx(dict(dict.fromkeys(attribution.LAYERS, 0.0),
                                     **want))


def test_operations_outside_the_window_are_cut():
    got = idle_by_layer(WINDOW, [(-10.0, 5.0), (95.0, 120.0)], [])
    assert got["unspanned"] == pytest.approx(90.0)


def _measured(key="a=1", calls=2):
    # one kernel.measure span of 1 s holding two calls of 0.2 s each
    return Attribution(
        window_s=1.0, idle_s=dict.fromkeys(attribution.LAYERS, 0.0),
        spans=[("kernel.build", -1e9, 0.0, {}),
               ("kernel.measure", 0.0, 1e9, {"key": key, "calls": calls})],
        busy=[(1e8, 3e8), (5e8, 7e8)])


def test_objective_excess_over_device_time_per_call():
    ring = [{"name": "kernel.build", "ts": 0.0, "args": {"key": "a=1"}},
            {"name": "kernel.measure", "ts": 1.0,
             "args": {"key": "a=1", "best_s": 0.21}}]
    assert _measured().objective_excess(ring) == pytest.approx(5.0)


@pytest.mark.parametrize("ring", [
    [],                                                    # none recorded
    [{"name": "kernel.measure", "ts": 1.0,
      "args": {"key": "a=2", "best_s": 0.21}}],            # another config
    [{"name": "kernel.measure", "ts": t, "args": {"key": "a=1",
                                                  "best_s": 0.21}}
     for t in (1.0, 2.0)],                                 # one too many
])
def test_objective_excess_needs_the_same_configs(ring):
    assert _measured().objective_excess(ring) is None


def test_no_device_trace_reads_no_file(tmp_path):
    ctx = SimpleNamespace(trace=None, cell={"name": "x.campaign"})
    assert attribution.of(ctx, tmp_path) is None


def test_program_spans_have_layers():
    assert attribution.layer_of("kernel.lower") == "compile"
    assert attribution.layer_of("kernel.measure") == "measure"
    assert attribution.layer_of("journal.publish") == "session"
    assert attribution.layer_of("bench.session") is None
    assert attribution.layer_of("session") is None


# a traced campaign of two configs of flash attention at hq 4, hkv 2,
# tq = tk = 1024, d 128, recorded on a TPU v5e inside bench.window and
# bench.session, with the program's ring buffer beside it
FIXTURES = Path(__file__).resolve().parents[1] / "fixtures"


@pytest.fixture(scope="module")
def recorded():
    path = FIXTURES / "campaign2.xplane.pb"
    lines = (FIXTURES / "campaign2.spans.jsonl").read_text().splitlines()
    ring = [json.loads(x) for x in lines[1:]]
    return attribution.reduce(path), xtrace.reduce(path), ring


def test_recorded_idle_split_adds_up_to_the_reduction(recorded):
    got, reduced, _ = recorded
    assert got.window_s == pytest.approx(reduced.window_s)
    idle = 100.0 * (1.0 - reduced.busy_s / reduced.window_s)
    assert sum(got.idle_share(x) for x in attribution.LAYERS) == \
        pytest.approx(idle, abs=1e-9)
    # the chip waits on the compiles
    assert got.idle_share("compile") > 90.0


def test_recorded_spans_keep_their_nesting_and_arguments(recorded):
    got, _, ring = recorded
    lower = got.durations_s("kernel.lower")
    build = got.durations_s("kernel.build")
    assert len(lower) == len(build) == 2
    assert all(a < b for a, b in zip(lower, build))
    keys = [args["key"] for n, _, _, args in got.spans
            if n == "kernel.measure"]
    assert keys == [r["args"]["key"] for r in ring
                    if r["name"] == "kernel.measure"]
    # a tiny kernel: the host's best call is mostly dispatch and sync
    assert got.objective_excess(ring) > 0
