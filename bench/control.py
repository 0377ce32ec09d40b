#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python bench/control.py --config <config> --seeds 1,2,3

on the chip, from the root of a checkout.  For each seed it makes the
configuration's inputs at its full size and prints one JSON line with the
rel-L2 error, against the plain reference, of the public op with its
default config (``program``) and of the reference computed in the
precision below the configuration's (``control``, float8 e4m3 for bf16).
The benchmark's own runs never run the control; a limit lies between the
program's largest reading and the control's smallest.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(catalog, config_name: str, seeds, *, rehearse: bool = False):
    import jax

    from harness.cell import compare, seed_key

    config = catalog.config(config_name)
    kernel, shape = config["kernel"], dict(config["shape"])
    adapter, ref = catalog.kernel(kernel), catalog.reference(kernel)
    for seed in seeds:
        x = jax.block_until_ready(ref.inputs(seed_key(jax, seed), shape))
        call = adapter.deploy(x, shape, interpret=rehearse)
        errors = compare(jax, ref, x, shape, [
            ("program", call), ("control", lambda: ref.control(x, shape))])
        yield {"config": config_name, "seed": seed, **errors}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax

    from harness.catalog import Catalog
    from harness.cell import compile_cache_dir, configure_compile_cache
    from harness.device import NoAccelerator, require_tpu

    try:
        require_tpu(jax, 1)
    except NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    configure_compile_cache(jax, compile_cache_dir(ROOT))
    for row in readings(Catalog(), args.config,
                        [int(s) for s in args.seeds.split(",")]):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
