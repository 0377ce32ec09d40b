"""Operations and bytes that absorbed MLA decode needs, from the shape
alone: a cached token past its sequence's length is not work, and a cache
tile streamed more than once is read once."""


def cached_tokens(shape: dict) -> int:
    return sum(shape["lengths"])


def flops(shape: dict) -> float:
    """Per head and cached token: the scores' 2 (c + r) operations and the
    weighted sum's 2 c."""
    return 2.0 * shape["h"] * (2 * shape["c"] + shape["r"]) \
        * cached_tokens(shape)


def hbm_bytes(shape: dict) -> float:
    """The live cache (c_kv and k_pe) read once, q_lat, q_pe and the output
    once; bf16."""
    b, h, c, r = shape["b"], shape["h"], shape["c"], shape["r"]
    return 2.0 * ((c + r) * cached_tokens(shape) + b * h * (c + r + c))
