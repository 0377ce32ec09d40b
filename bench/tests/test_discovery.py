"""The harness finds each part by name, from a file of its own: adding a
configuration, a traffic mix, a metric and a cell takes new files and
entries, and no edit to a file that exists."""

import json
import shutil

import pytest

from harness.catalog import (BENCH, Catalog, UnknownPart, find_cell,
                             metrics_of)


def test_parts_added_under_a_new_directory_are_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    base = tmp_path / "bench"
    (base / "configs" / "new-model.json").write_text(json.dumps(
        {"kernel": "gemm", "shape": {"m": 8, "n": 8, "k": 8}}))
    (base / "traffic" / "campaign_annealing.json").write_text(json.dumps(
        {"kind": "sessions", "tuner": "annealing", "budget": 8,
         "workers": 1, "session_seeds": [1]}))
    (base / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (base / "limits" / "new-model.campaign_annealing.json").write_text(
        json.dumps({"rel_l2": 0.01}))

    cat = Catalog(base)
    assert cat.config("new-model")["shape"]["m"] == 8
    assert cat.traffic("campaign_annealing")["tuner"] == "annealing"
    assert cat.metric("new_metric").read(None) == 42.0
    assert cat.limits("new-model.campaign_annealing") == {"rel_l2": 0.01}
    assert cat.work("gemm").flops({"m": 8, "n": 8, "k": 8}) == 1024
    assert hasattr(cat.kernel("gemm"), "deploy")
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_metrics_are_chosen_by_cell():
    bench = {"workloads": [{"name": "a.x"}, {"name": "b.y"}],
             "end_to_end": [{"name": "rate", "workloads": ["a.x"]},
                            {"name": "ms", "workloads": ["b.y"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "p", "moves": "rate",
                            "workloads": ["a.x"]},
                           {"name": "q", "moves": "ms"}]}
    cell = find_cell(bench, "b.y")
    assert [m["name"] for m in metrics_of(bench, "end_to_end", cell)] == \
        ["ms", "setup_s"]
    assert [m["name"] for m in metrics_of(bench, "per_layer", cell)] == ["q"]


def test_an_unknown_name_is_an_error():
    cat = Catalog()
    with pytest.raises(UnknownPart):
        cat.config("no-such-model")
    with pytest.raises(UnknownPart):
        cat.metric("../run")
