"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the result line.

``run_cell`` is what ``bench/run.py`` calls.  Its keyword ``rehearse``
lets the CPU tests drive the same code without a TPU: it skips the look
for a chip and the peaks table, and runs the kernels in interpret mode.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from . import device as devmod
from . import traffic as traffic_mod
from . import xtrace
from .catalog import (Catalog, UnknownPart, find_cell, load_benchmark,
                      metrics_of)

#: where runs keep their compile cache, journals and profiles, under the
#: checkout's root (listed in .gitignore)
STATE = Path("experiments") / "bench"


@dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: dict
    config: dict
    shape: dict
    kernel: str
    trace_names: tuple
    traffic: dict
    window: traffic_mod.Window
    #: the program's telemetry spans in the window: name, ts and dur in us
    spans: list[dict] = field(default_factory=list)
    #: the reduced profiler trace of the window
    trace: xtrace.Reduced | None = None
    peaks: dict | None = None
    flops: float = 0.0
    hbm_bytes: float = 0.0


def seed_key(jax, seed: int):
    """A key from any non-negative whole number of up to 64 bits."""
    key = jax.random.key(0)
    for part in ((seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF):
        key = jax.random.fold_in(key, jax.numpy.uint32(part))
    return key


def compile_cache_dir(root: Path) -> Path:
    return Path(root) / STATE / "jax_cache"


def configure_compile_cache(jax, directory: Path) -> None:
    """JAX's persistent cache at ``directory``, holding every compile,
    however short or small.  The program keeps its cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, so that is set too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(directory)
    jax.config.update("jax_compilation_cache_dir", str(directory))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def set_compile_cache(jax, on: bool) -> None:
    """Turn the persistent cache on or off for the compiles that follow."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, catalog: Catalog | None = None,
             rehearse: bool = False, out=None, err=None) -> int:
    out = out or sys.stdout
    err = err or sys.stderr
    root = Path(root)
    catalog = catalog or Catalog()
    bench = load_benchmark(root)
    cell = find_cell(bench, workload)
    config = catalog.config(cell["config"])
    params = catalog.traffic(cell["traffic"])
    limits = catalog.limits(cell["name"])
    kernel = config["kernel"]
    shape = dict(config["shape"])
    adapter = catalog.kernel(kernel)
    ref = catalog.reference(kernel)
    work = catalog.work(kernel)

    import jax

    if rehearse:
        dev = devmod.describe(jax.devices()[:cell["chips"]])
        peaks = None
    else:
        dev = devmod.require_tpu(jax, cell["chips"])
        peaks = catalog.peaks(dev["kind"])
    devices = jax.devices()[:cell["chips"]]
    configure_compile_cache(jax, compile_cache_dir(root))
    state = root / STATE / cell["name"]
    profile_dir = state / "profile"
    shutil.rmtree(profile_dir, ignore_errors=True)

    x = jax.block_until_ready(ref.inputs(seed_key(jax, seed), shape))
    setup = {}

    @contextlib.contextmanager
    def window():
        setup["s"] = devmod.process_age_s()
        if trace:
            from repro import telemetry
            # device operations and annotations; no Python call tracing
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(str(profile_dir),
                                     profiler_options=options)
            telemetry.trace.clear()
            telemetry.enable()
        try:
            with jax.profiler.TraceAnnotation(xtrace.WINDOW):
                yield
        finally:
            if trace:
                telemetry.disable()
                jax.profiler.stop_trace()

    win = traffic_mod.run(
        params, seconds=seconds, seed=seed, window=window,
        annotate=jax.profiler.TraceAnnotation,
        compile_cache=lambda on: set_compile_cache(jax, on),
        kernel=adapter, inputs=x, shape=shape, interpret=rehearse,
        store_root=state / "sessions")
    memory_peak = devmod.memory_peak_bytes(devices)

    # the comparison that decides `correct`, once the window has closed
    errors = compare(jax, ref, x, shape, win.outputs)
    checks = dict(win.counts)
    for name in ("rel_l2", "max_err"):
        checks[name] = max((e[name] for e in errors.values()),
                           default=math.inf)
    for label, e in errors.items():
        print(f"output {label}: rel_l2 {e['rel_l2']!r} max_err "
              f"{e['max_err']!r}", file=err)
    unknown = set(checks) - set(limits)
    if unknown:
        raise UnknownPart(f"no limit for {sorted(unknown)} in "
                          f"limits/{cell['name']}.json")
    correct = all(math.isfinite(v) and v <= limits[k]
                  for k, v in checks.items())
    failed = win.failed
    if win.outputs_fail:     # a measured config whose output is wrong
        failed += sum(not (e["rel_l2"] <= limits["rel_l2"]
                           and e["max_err"] <= limits["max_err"])
                      for e in errors.values())

    result: dict[str, Any] = {"correct": correct, "attempted": win.attempted,
                              "failed": failed}
    device_out = dict(dev, memory_peak_bytes=memory_peak)
    if not trace:
        values = dict(win.values, setup_s=setup["s"])
        metrics = {}
        for m in metrics_of(bench, "end_to_end", cell):
            if m["name"] not in values:
                raise UnknownPart(f"{m['name']} is not measured by traffic "
                                  f"kind {params['kind']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from repro import telemetry
        path = xtrace.find_trace(profile_dir)
        reduced = xtrace.reduce(path) if path else None
        if reduced is None and not rehearse:
            raise RuntimeError(f"the trace under {profile_dir} holds no "
                               f"TPU operation")
        ctx = Context(cell=cell, config=config, shape=shape, kernel=kernel,
                      trace_names=tuple(adapter.TRACE_NAMES), traffic=params,
                      window=win, spans=telemetry.trace.events(),
                      trace=reduced, peaks=peaks, flops=work.flops(shape),
                      hbm_bytes=work.hbm_bytes(shape))
        metrics = {}
        for m in metrics_of(bench, "per_layer", cell):
            value = catalog.metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device_out.update(busy_s=reduced.busy_s,
                              window_s=reduced.window_s)
            result["breakdown"] = reduced.breakdown()
    result["metrics"] = metrics
    result["device"] = device_out
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}

    print(f"window: {win.seconds!r} s, attempted {win.attempted}, "
          f"failed {failed}, setup {setup['s']!r} s", file=err)
    for k, v in checks.items():
        verdict = "ok" if math.isfinite(v) and v <= limits[k] else "FAIL"
        print(f"check {k}: {v!r} limit {limits[k]!r} {verdict}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def compare(jax, ref, x: dict, shape: dict,
            outputs) -> dict[str, dict[str, float]]:
    """Each output's error against the plain reference: ``rel_l2``, the
    norm of the difference over the reference's norm, and ``max_err``, the
    widest gap of one element over the reference's root mean square."""
    import jax.numpy as jnp

    @jax.jit
    def errors_of(got, want):
        d = got.astype(jnp.float32) - want
        rms = jnp.sqrt(jnp.mean(want * want))
        return (jnp.sqrt(jnp.mean(d * d)) / rms, jnp.max(jnp.abs(d)) / rms)

    want = ref.reference(x, shape)
    errors = {}
    for label, y in outputs:
        try:
            rel, gap = errors_of(y() if callable(y) else y, want)
            errors[label] = {"rel_l2": float(rel), "max_err": float(gap)}
        except Exception as e:              # an output that cannot be had
            print(f"output {label}: {e!r:.400}", file=sys.stderr)
            errors[label] = {"rel_l2": math.inf, "max_err": math.inf}
    return errors
