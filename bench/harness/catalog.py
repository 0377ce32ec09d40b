"""Finds the benchmark's parts by name, each in a file of its own.

Under the benchmark's directory (``bench/`` in this repository):

- ``configs/<config>.json``: the configuration as it is run;
- ``traffic/<traffic>.json``: the parameters of a traffic mix, read by
  ``harness/traffic.py``;
- ``metrics/<metric>.py``: the reader of one per-layer metric;
- ``kernels/<kernel>.py``: how the benchmark calls the program's kernel;
- ``reference/<kernel>.py``: the kernel's plain reference, inputs and
  control;
- ``work/<kernel>.py``: the kernel's operations and bytes from its shape;
- ``limits/<cell>.json``: the limits of the numbers a cell compares;
- ``peaks.json``: the chips' peaks, keyed by ``device_kind``.

Adding a part is adding its file and naming it in ``BENCHMARK.json``; no
existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

#: the benchmark's own directory
BENCH = Path(__file__).resolve().parents[1]

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownPart(LookupError):
    """A name with no file behind it."""


class Catalog:
    """The parts under ``base`` (default: this benchmark's directory)."""

    def __init__(self, base: Path | None = None):
        self.base = Path(base) if base is not None else BENCH
        self._modules: dict[Path, ModuleType] = {}

    def _path(self, kind: str, name: str, suffix: str) -> Path:
        if not _NAME.match(name):
            raise UnknownPart(f"{kind} name {name!r} is not a valid name")
        path = self.base / kind / f"{name}{suffix}"
        if not path.is_file():
            raise UnknownPart(f"no {kind} named {name!r} (looked for {path})")
        return path

    def _json(self, kind: str, name: str) -> dict:
        with open(self._path(kind, name, ".json")) as f:
            return json.load(f)

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self._path(kind, name, ".py")
        mod = self._modules.get(path)
        if mod is None:
            modname = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
            spec = importlib.util.spec_from_file_location(modname, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return mod

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> dict:
        return self._json("limits", cell)

    def metric(self, name: str) -> ModuleType:
        return self._module("metrics", name)

    def kernel(self, name: str) -> ModuleType:
        return self._module("kernels", name)

    def reference(self, name: str) -> ModuleType:
        return self._module("reference", name)

    def work(self, name: str) -> ModuleType:
        return self._module("work", name)

    def peaks(self, device_kind: str) -> dict:
        """The peaks of ``device_kind``; an unknown device is an error."""
        with open(self.base / "peaks.json") as f:
            table = json.load(f)
        try:
            return table["devices"][device_kind]
        except KeyError:
            raise UnknownPart(
                f"no peaks for device kind {device_kind!r} in "
                f"{self.base / 'peaks.json'} (known: "
                f"{', '.join(sorted(table['devices']))})") from None


def load_benchmark(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise UnknownPart(f"no workload named {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, section: str, cell: dict) -> list[dict]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    ``cell`` reports.  A metric with a ``workloads`` list is reported in
    those cells.  Without one, an end-to-end metric is reported in every
    cell, and a per-layer metric in every cell that reports the end-to-end
    metric it moves."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if section == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]
