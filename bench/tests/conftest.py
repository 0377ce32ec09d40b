"""Shared set-up of the benchmark's CPU tests: the harness on the path, a
copy of the benchmark at tiny shapes, and the kernels in interpret mode."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: shapes small enough for interpret mode, with the same structure
TINY = {"dsc33b-attn8k": {"hq": 4, "hkv": 2, "tq": 256, "tk": 256, "d": 128},
        "dsc33b-up2k": {"m": 256, "n": 384, "k": 512}}


@pytest.fixture
def tiny(tmp_path):
    """A checkout root whose benchmark runs at the shapes of TINY, with
    campaigns of two sessions of two configs."""
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "fixtures"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for name, shape in TINY.items():
        _update(tmp_path / "bench" / "configs" / f"{name}.json",
                lambda c: c["shape"].update(shape))
    _update(tmp_path / "bench" / "traffic" / "campaign.json",
            lambda c: c.update(budget=2, session_seeds=[1, 2]))
    return tmp_path


def _update(path: Path, change) -> None:
    c = json.loads(path.read_text())
    change(c)
    path.write_text(json.dumps(c))


@pytest.fixture
def interpret(monkeypatch):
    """Every kernel the program compiles runs in Pallas interpret mode."""
    import jax

    from repro.kernels.common import KernelProblem, _split_inputs

    def lower(self, config, inputs):
        arrays, consts = _split_inputs(inputs)
        return jax.jit(lambda a: self.run_kernel(
            config, {**consts, **a}, interpret=True)).lower(arrays)

    monkeypatch.setattr(KernelProblem, "lower_kernel", lower)


def run_tiny(root: Path, cell: str, *, trace: bool = False, seed: int = 7,
             seconds: float = 0.5, catalog=None) -> dict:
    """One rehearsed run of ``cell``; returns its last line, parsed."""
    import io

    from harness.catalog import Catalog
    from harness.cell import run_cell

    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(root, cell, seed, seconds, trace,
                  catalog=catalog or Catalog(root / "bench"), rehearse=True,
                  out=out, err=err)
    assert rc == 0, err.getvalue()
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    result["_stderr"] = err.getvalue()
    return result
