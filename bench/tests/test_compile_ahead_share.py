"""compile_ahead_share, read from hand-written span records as the
program's ring buffer gives them."""

from types import SimpleNamespace

import pytest

from harness.catalog import Catalog


def _read(spans, trace=True):
    return Catalog().metric("compile_ahead_share").read(
        SimpleNamespace(spans=spans, trace=object() if trace else None))


def _compile(ahead=None):
    args = {"key": "a=1"} if ahead is None else {"key": "a=1", "ahead": ahead}
    return {"name": "kernel.compile", "cat": "kernel", "ts": 0.0, "dur": 1.0,
            "args": args}


OTHERS = [{"name": "kernel.build", "ts": 0.0, "dur": 2.0, "args": {}},
          {"name": "kernel.lower", "ts": 0.0, "dur": 1.0}]


@pytest.mark.parametrize("spans,want", [
    ([_compile(0)] * 4 + OTHERS, 0.0),
    # a program whose spans carry no ``ahead``
    ([_compile()] * 4 + OTHERS, 0.0),
    # four sessions of eight: the first of each inline, seven ahead
    (([_compile(0)] + [_compile(1)] * 7) * 4 + OTHERS, 87.5),
    (OTHERS, None),
    ([], None),
])
def test_share_of_compiles_ahead(spans, want):
    assert _read(spans) == want


def test_no_share_without_a_device_trace():
    assert _read([_compile(1)] * 4, trace=False) is None
