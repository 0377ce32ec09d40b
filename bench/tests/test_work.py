"""Work counts and the peaks table."""

import pytest

from harness.catalog import Catalog, UnknownPart


def test_attention_counts_by_hand():
    work = Catalog().work("flash_attention")
    shape = {"hq": 4, "hkv": 2, "tq": 4, "tk": 4, "d": 8, "causal": True}
    # causal 4x4 keeps 1 + 2 + 3 + 4 = 10 (query, key) pairs
    assert work.pairs(shape) == 10
    assert work.flops(shape) == 4 * 4 * 8 * 10
    # q and out: 4*4*8 each; k and v: 2*4*8 each; bf16
    assert work.hbm_bytes(shape) == 2 * (128 + 64 + 64 + 128)


def test_attention_pairs_when_the_cache_is_longer():
    work = Catalog().work("flash_attention")
    # 2 queries at the end of 5 keys see 4 and 5 keys
    assert work.pairs({"tq": 2, "tk": 5, "causal": True}) == 9
    assert work.pairs({"tq": 2, "tk": 5, "causal": False}) == 10


def test_attention_counts_at_the_cell_shape():
    work = Catalog().work("flash_attention")
    shape = Catalog().config("dsc33b-attn8k")["shape"]
    assert work.flops(shape) == 4 * 56 * 128 * 8192 * 8193 // 2


def test_gemm_counts_by_hand():
    work = Catalog().work("gemm")
    shape = {"m": 2, "n": 3, "k": 4, "beta": 0.0}
    assert work.flops(shape) == 2 * 2 * 3 * 4
    assert work.hbm_bytes(shape) == 2 * (8 + 12 + 6)
    assert work.hbm_bytes(dict(shape, beta=0.5)) == 2 * (8 + 12 + 6 + 6)


def test_v5e_peaks():
    peaks = Catalog().peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(UnknownPart, match="no peaks for device kind"):
        Catalog().peaks("TPU v99")
