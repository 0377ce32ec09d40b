"""Tunable Pallas TPU kernels — the BAT 2.0 benchmark set, TPU-adapted.

Seven paper kernels, flash attention and DeepSeek-V2's MLA decode
(absorbed latent attention over a ragged batch of caches), each a
:class:`TunableProblem`.
"""

from .attention import AttentionProblem, flash_attention
from .conv2d import Conv2dProblem, conv2d
from .dedisp import DedispProblem, dedisp
from .expdist import ExpdistProblem, expdist
from .hotspot import HotspotProblem, hotspot
from .matmul import GemmProblem, gemm
from .mla import MLADecodeProblem, mla_decode
from .nbody import NbodyProblem, nbody
from .pnpoly import PnpolyProblem, pnpoly

#: the benchmark registry (name -> problem class); order follows the paper
BENCHMARKS = {
    "gemm": GemmProblem,
    "nbody": NbodyProblem,
    "hotspot": HotspotProblem,
    "pnpoly": PnpolyProblem,
    "conv2d": Conv2dProblem,
    "expdist": ExpdistProblem,
    "dedisp": DedispProblem,
    "flash_attention": AttentionProblem,
    "mla_decode": MLADecodeProblem,
}

#: paper protocol: exhaustive where tractable, 10k samples otherwise
EXHAUSTIVE = ("pnpoly", "nbody", "gemm", "conv2d", "flash_attention")

__all__ = ["BENCHMARKS", "EXHAUSTIVE", "GemmProblem", "Conv2dProblem",
           "NbodyProblem", "HotspotProblem", "PnpolyProblem",
           "ExpdistProblem", "DedispProblem", "AttentionProblem",
           "MLADecodeProblem",
           "gemm", "conv2d", "nbody", "hotspot", "pnpoly", "expdist",
           "dedisp", "flash_attention", "mla_decode"]
