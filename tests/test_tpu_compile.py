"""Compile the main-path kernels for a TPU v5e that is described, not
attached.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology without a chip, so these tests see what interpret mode
cannot: block shapes the chip refuses, VMEM over-use, Pallas ops with no
Mosaic lowering.  Each test compiles one kernel at its ``default_shape``
(flash attention at qwen3-8b's widths, GEMM 4096^3 bf16, nbody, hotspot,
MLA decode at DeepSeek-V2's widths over 64 sequences of a 64k-token cache)
with its ``ops.py`` default config; the first four exactly as
``chip_smoke.py`` runs them, MLA decode as ``dsv2-mla-dec64.deploy`` does.
The attention op's default also compiles at DeepSeek-Coder-33B's group of 7
(as ``dsc33b-attn8k.deploy`` calls it) and at a group of 8.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Keep every test that needs it in this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import ops as attention_ops
from repro.kernels.attention.kernel import tiling
from repro.kernels.attention.space import AttentionProblem
from repro.kernels.conv2d import ops as conv2d_ops
from repro.kernels.conv2d.space import Conv2dProblem
from repro.kernels.dedisp import ops as dedisp_ops
from repro.kernels.dedisp.space import DedispProblem
from repro.kernels.expdist import ops as expdist_ops
from repro.kernels.expdist.space import ExpdistProblem
from repro.kernels.hotspot import ops as hotspot_ops
from repro.kernels.hotspot.space import HotspotProblem
from repro.kernels.matmul import ops as matmul_ops
from repro.kernels.matmul.space import GemmProblem
from repro.kernels.mla import ops as mla_ops
from repro.kernels.mla.space import MLADecodeProblem
from repro.kernels.nbody import ops as nbody_ops
from repro.kernels.nbody.space import NbodyProblem
from repro.kernels.pnpoly import ops as pnpoly_ops
from repro.kernels.pnpoly.space import PnpolyProblem

#: the kernels of the measured path: (problem, ops default config)
MAIN_PATH = {
    "attention": (AttentionProblem, attention_ops.DEFAULT_CONFIG),
    "gemm": (GemmProblem, matmul_ops.DEFAULT_CONFIG),
    "nbody": (NbodyProblem, nbody_ops.DEFAULT_CONFIG),
    "hotspot": (HotspotProblem, hotspot_ops.DEFAULT_CONFIG),
    "mla_decode": (MLADecodeProblem, mla_ops.DEFAULT_CONFIG),
}

#: kernels the v5e compiler refuses at their defaults, with the words of
#: its reason.  Each needs its lane-dim data movement redesigned; when one
#: compiles, its case fails here and it moves to MAIN_PATH.
REFUSED = {
    # dynamic_slice inside the kernel body (kernel.py:41)
    "conv2d": (Conv2dProblem, conv2d_ops.DEFAULT_CONFIG,
               "Unimplemented primitive .* dynamic_slice"),
    # slopes[0, v] with a traced v (kernel.py:39)
    "pnpoly": (PnpolyProblem, pnpoly_ops.DEFAULT_CONFIG,
               "Unimplemented primitive .* dynamic_slice"),
    # input block (block_c, T) is not (8, 128)-aligned (kernel.py:102)
    "dedisp": (DedispProblem, dedisp_ops.DEFAULT_CONFIG,
               r"divisible by 8 and 128.*\(block_size=4\)"),
    # output block (1, 1) on a (grid_i, 1) array (kernel.py:112)
    "expdist": (ExpdistProblem, expdist_ops.DEFAULT_CONFIG,
                r"divisible by 8 and 128.*array shape \(256, 1\)"),
}

#: an attention config the space admits (its structural VMEM budget is
#: 256 MiB) but whose working set the v5e compiler refuses
VMEM_REFUSED = {"block_q": 1024, "block_kv": 2048, "block_h": 4,
                "skip_masked": 1, "acc_dtype": "f32"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _described_inputs(prob, sharding) -> dict:
    """``prob.make_inputs(small=False)`` with every array replaced by its
    shape on the described chip; flags and counts stay as they are."""
    consts: dict = {}

    def arrays(key):
        ins = prob.make_inputs(key, small=False)
        consts.update({k: v for k, v in ins.items() if not hasattr(v, "shape")})
        return {k: v for k, v in ins.items() if hasattr(v, "shape")}

    shapes = jax.eval_shape(arrays, jax.random.key(0))
    return {**consts,
            **{k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
               for k, v in shapes.items()}}


def _compile(prob, config, sharding):
    return prob.lower_kernel(config, _described_inputs(prob, sharding)) \
        .compile()


@pytest.mark.parametrize("name", sorted(MAIN_PATH))
def test_main_path_kernel_compiles_for_v5e(name, one_chip):
    cls, config = MAIN_PATH[name]
    compiled = _compile(cls(), config, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_attention_vmem_overuse_is_refused(one_chip):
    prob = AttentionProblem()
    assert prob.space.satisfies(VMEM_REFUSED)
    with pytest.raises(Exception,
                       match="Ran out of memory in memory space vmem"):
        _compile(prob, VMEM_REFUSED, one_chip)


@pytest.mark.parametrize("hq", [56, 64])
def test_attention_default_takes_the_whole_group(hq, one_chip):
    """The deployed op at DeepSeek-Coder-33B's prefill (56 query heads over
    8 kv heads, a group of 7, 8k tokens) and at a group of 8 compiles with
    one program per kv head: the grid is (8, tq / bq, tk / bkv)."""
    t, d = 8192, 128
    q = jax.ShapeDtypeStruct((hq, t, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, t, d), jnp.bfloat16, sharding=one_chip)
    cfg = attention_ops.DEFAULT_CONFIG
    (bh, bq, bkv), grid = tiling(q.shape, kv.shape, block_q=cfg["block_q"],
                                 block_kv=cfg["block_kv"],
                                 block_h=cfg["block_h"])
    assert bh == hq // 8
    assert grid == (8, t // bq, t // bkv)
    compiled = jax.jit(attention_ops.attention).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_refused_kernel_names_compiler_reason(name, one_chip):
    cls, config, reason = REFUSED[name]
    with pytest.raises(Exception, match=reason):
        _compile(cls(), config, one_chip)
