"""The MLA decode cell rehearsed through the harness's own code, at a tiny
copy of its configuration in interpret mode on the CPU, and its work counts
checked by hand."""

import json

from conftest import run_tiny

from harness.catalog import Catalog

#: a tiny copy of dsv2-mla-dec64: one length past a tile edge, one full
TINY_MLA = {"b": 2, "h": 8, "c": 128, "r": 64, "t_max": 256,
            "lengths": [100, 256]}


def _tiny_mla(root):
    path = root / "bench" / "configs" / "dsv2-mla-dec64.json"
    config = json.loads(path.read_text())
    config["shape"].update(TINY_MLA)
    path.write_text(json.dumps(config))
    return root


def test_mla_cell_end_to_end_line(tiny):
    r = run_tiny(_tiny_mla(tiny), "dsv2-mla-dec64.deploy")
    assert r["correct"] is True, r["_stderr"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"kernel_ms", "setup_s"}
    assert set(r["checks"]) == {"rel_l2", "max_err", "failed_calls"}
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


def test_mla_traced_run_leaves_device_metrics_out(tiny):
    # off the TPU the trace holds no device plane: the roofline and the
    # idle share are left out, never reported as 0
    r = run_tiny(_tiny_mla(tiny), "dsv2-mla-dec64.deploy", trace=True)
    assert r["metrics"] == {}


def test_mla_counts_by_hand():
    work = Catalog().work("mla_decode")
    shape = dict(TINY_MLA)
    tokens = 100 + 256
    # per head and cached token: scores 2 (128 + 64), weighted sum 2 * 128
    assert work.flops(shape) == 8 * (2 * 192 + 2 * 128) * tokens
    # the live cache once (192 bf16 per token), q_lat, q_pe and the output
    assert work.hbm_bytes(shape) == 2 * (192 * tokens + 2 * 8 * (128 + 64
                                                                + 128))


def test_mla_counts_at_the_cell_shape():
    work = Catalog().work("mla_decode")
    shape = Catalog().config("dsv2-mla-dec64")["shape"]
    assert sum(shape["lengths"]) == 1_402_538
    assert work.flops(shape) == 2 * 128 * (2 * 512 + 64) * 1_402_538
    # 242 operations a cached byte, 239 with the queries and output: the
    # v5e's ridge (197e12 / 819e9 = 240)
    assert 238 < work.flops(shape) / work.hbm_bytes(shape) < 242
