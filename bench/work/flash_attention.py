"""Operations and bytes that causal grouped-query attention needs, from the
shape alone: whatever implements it, masked tiles it computes anyway and
tiles it streams twice are not work."""


def pairs(shape: dict) -> int:
    """(query, key) pairs the mask keeps: query i sees keys up to
    i + (tk - tq)."""
    tq, tk = shape["tq"], shape["tk"]
    if not shape.get("causal", True):
        return tq * tk
    off = tk - tq
    return sum(min(tk, i + off + 1) for i in range(tq))


def flops(shape: dict) -> float:
    """Two matmuls (q k^T and p v) of 2 d operations per pair per head."""
    return 4.0 * shape["hq"] * shape["d"] * pairs(shape)


def hbm_bytes(shape: dict) -> float:
    """q, k and v read once and the output written, in bf16."""
    hq, hkv, tq, tk, d = (shape[k] for k in ("hq", "hkv", "tq", "tk", "d"))
    return 2.0 * (hq * tq * d + 2 * hkv * tk * d + hq * tq * d)
