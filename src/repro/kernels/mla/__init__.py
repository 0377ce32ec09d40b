from .ops import mla_decode
from .ref import mla_decode_reference
from .space import MLADecodeProblem

__all__ = ["mla_decode", "mla_decode_reference", "MLADecodeProblem"]
