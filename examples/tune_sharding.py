"""Beyond-paper: autotune the DISTRIBUTION config the way BAT tunes kernels.

    PYTHONPATH=src python examples/tune_sharding.py

The sharding plan of a training step — mesh aspect (data vs model ways),
gradient-accumulation depth, remat policy — is a discrete constrained
search space, exactly like a kernel's.  The objective is the dominant
three-term roofline time extracted from the *compiled* step (the suite's
RooflineEvaluator; see repro/roofline).  This is the paper's methodology
applied one level up the stack.

Runs on 8 forced host devices with a reduced model (compiles in seconds);
the identical problem definition tunes the production 16x16 mesh on TPU.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402

from repro.configs import ARCHS, reduce_config  # noqa: E402
from repro.core.problem import FunctionProblem  # noqa: E402
from repro.core.space import Constraint, Param, SearchSpace  # noqa: E402
from repro.core.tuners import GridSearch, run_tuner  # noqa: E402
from repro.launch.steps import lower_cell, plan_cell  # noqa: E402
from repro.roofline import HW, collective_bytes  # noqa: E402

import dataclasses  # noqa: E402

N_DEV = 8
ARCH = "granite-moe-3b-a800m"        # MoE: sharding actually matters


def build_space() -> SearchSpace:
    return SearchSpace(
        [Param("model_ways", (1, 2, 4, 8)),
         Param("microbatches", (1, 2, 4)),
         Param("remat", (0, 1))],
        [Constraint("fits_mesh", lambda c: N_DEV % c["model_ways"] == 0)],
        name="sharding")


def objective(config, arch_name: str) -> float:
    cfg = reduce_config(ARCHS[ARCH])
    cfg = dataclasses.replace(cfg, remat=bool(config["remat"]))
    model_ways = config["model_ways"]
    mesh = jax.make_mesh((N_DEV // model_ways, model_ways),
                         ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    try:
        plan = plan_cell(cfg, "train_4k", mesh,
                         microbatches=config["microbatches"])
        # reduced shape cell: shrink the batch/seq to example scale
        batch = {k: jax.ShapeDtypeStruct((8,) + v.shape[1:], v.dtype)
                 for k, v in plan.args[-1].items()}
        batch = {k: jax.ShapeDtypeStruct((v.shape[0], 128), v.dtype)
                 for k, v in batch.items()}
        plan = dataclasses.replace(plan, args=plan.args[:-1] + (batch,),
                                   in_shardings=plan.in_shardings[:-1]
                                   + (None,))
        compiled = lower_cell(plan, mesh).compile()
    except Exception as e:                      # invalid plan == inf
        print(f"  config {config}: INVALID ({type(e).__name__})")
        return float("inf")
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    t_c = float(ca.get("flops", 0.0)) / HW["peak_flops_bf16"]
    t_m = float(ca.get("bytes accessed", 0.0)) / HW["hbm_bw"]
    t_x = collective_bytes(compiled.as_text())["total"] / HW["ici_bw"]
    t = max(t_c, t_m, t_x)
    print(f"  config {config}: dominant term {t * 1e6:9.1f} us "
          f"(c={t_c * 1e6:.1f} m={t_m * 1e6:.1f} x={t_x * 1e6:.1f})")
    return t


def main() -> None:
    space = build_space()
    prob = FunctionProblem(space, objective, name="sharding-tune")
    print(f"search space: {space.cardinality} plans "
          f"({space.constrained_cardinality()} valid)")
    res = run_tuner(GridSearch(space, seed=0), prob, budget=32)
    print(f"\nbest plan: {res.best.config}  "
          f"dominant-term {res.best.objective * 1e6:.1f} us "
          f"(over {res.evaluations} compiled evaluations)")


if __name__ == "__main__":
    main()
