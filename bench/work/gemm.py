"""Operations and bytes that out = alpha * a @ b + beta * c needs, from the
shape alone."""


def flops(shape: dict) -> float:
    return 2.0 * shape["m"] * shape["n"] * shape["k"]


def hbm_bytes(shape: dict) -> float:
    """a and b read, c read where beta is not 0, the output written; bf16."""
    m, n, k = shape["m"], shape["n"], shape["k"]
    c_read = m * n if shape.get("beta", 1.0) else 0
    return 2.0 * (m * k + k * n + c_read + m * n)
