"""The shared problem interface — BAT 2.0's central contribution.

Every benchmark (and every framework component that wants autotuning — Pallas
kernels, sharding configs, remat policies) exposes itself as a
:class:`TunableProblem`:  a named :class:`SearchSpace` plus an evaluation
function producing a :class:`Trial`.  Every tuner consumes this interface
unmodified; adding a benchmark or a tuner never requires porting work —
exactly the interoperability argument of the paper.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Sequence

from ..telemetry.trace import span
from .costmodel import (ARCH_NAMES, DEFAULT_ARCH, FeatureBatch,
                        KernelFeatures, estimate_seconds,
                        estimate_seconds_batch)
from .space import Config, SearchSpace

#: below this many rows, columnar (numpy) evaluation loses to the scalar
#: feature math — batched endpoints fall back (identical results)
_COLUMNAR_MIN = 8


class Trial:
    """One evaluated configuration.

    ``config`` may be materialized lazily: row-native producers (the
    compiled-space evaluation endpoints, the journal-v2 replay path) pass
    ``row=``/``space=`` instead of a config dict, and the mixed-radix decode
    runs on first :attr:`config` access.  The session harness never touches
    ``config`` on its hot path, so trials whose configs no analysis reads
    are never decoded at all; :func:`materialize_configs` batch-decodes a
    trace in one numpy pass when something (trace publication, plotting)
    does want the dicts.

    Invariant: when both are given, ``row`` MUST be the flat index of
    ``config`` (``row == space.flat_index(config)``).  Row-aware consumers
    (``ResultTable.from_trials``) trust the row without re-encoding the
    dict, so a mismatched pair would publish the row's config.
    """

    __slots__ = ("objective", "arch", "valid", "info",
                 "_config", "_row", "_space")

    def __init__(self, config: Config | None, objective: float,
                 arch: str = DEFAULT_ARCH, valid: bool = True,
                 info: dict | None = None, *,
                 row: int | None = None, space: SearchSpace | None = None):
        if config is None and (row is None or space is None):
            raise ValueError("lazy Trial needs both row= and space=")
        self._config = config
        self._row = None if row is None else int(row)
        self._space = space
        self.objective = objective    # seconds; +inf => invalid on this arch
        self.arch = arch
        self.valid = valid
        self.info: dict = {} if info is None else info

    @property
    def config(self) -> Config:
        if self._config is None:
            self._config = self._space.from_flat_index(self._row)
        return self._config

    @property
    def row(self) -> int | None:
        """The compiled-space flat index, when this trial was produced (or
        journaled) row-natively — ``None`` for config-born trials."""
        return self._row

    @property
    def ok(self) -> bool:
        return self.valid and math.isfinite(self.objective)

    def __repr__(self) -> str:  # pragma: no cover
        cfg = self._config if self._config is not None else f"<row {self._row}>"
        return (f"Trial(config={cfg!r}, objective={self.objective!r}, "
                f"arch={self.arch!r}, valid={self.valid!r}, info={self.info!r})")


def materialize_configs(trials: Sequence[Trial]) -> None:
    """Decode every lazy trial's config in one batched pass per space.

    Equivalent to touching ``t.config`` on each trial, but through
    ``CompiledSpace.decode_many`` (one numpy pass per parameter column)
    instead of a scalar mixed-radix decode per trial."""
    pending: dict[int, tuple[SearchSpace, list[Trial]]] = {}
    for t in trials:
        if t._config is None:
            sp = t._space
            pending.setdefault(id(sp), (sp, []))[1].append(t)
    for sp, lazy in pending.values():
        comp = sp.compiled()
        if comp is None:
            for t in lazy:
                t._config = sp.from_flat_index(t._row)
        else:
            for t, cfg in zip(lazy, comp.decode_many([t._row for t in lazy])):
                t._config = cfg


class TunableProblem:
    """Base class: a search space + an objective.

    Subclasses implement :meth:`features` (analytical evaluation via the TPU
    cost model) and may override :meth:`evaluate` entirely (e.g. the
    roofline evaluator compiles HLO instead).
    """

    name: str = "problem"
    #: True when :meth:`features`/:meth:`feature_columns` ignore ``arch``
    #: (the architecture enters only at cost-model-estimate time) — lets
    #: multi-architecture sweeps build the feature columns once.
    arch_independent_features: bool = False

    def __init__(self, space: SearchSpace):
        self.space = space

    # -- analytical path ------------------------------------------------ #
    def features(self, config: Config, arch: str) -> KernelFeatures:
        raise NotImplementedError

    def evaluate(self, config: Config, arch: str = DEFAULT_ARCH) -> Trial:
        if not self.space.satisfies(config):
            return Trial(config, math.inf, arch, valid=False,
                         info={"violated": self.space.violated(config)})
        feats = self.features(config, arch)
        t = estimate_seconds(feats, arch)
        return Trial(config, t, arch, valid=math.isfinite(t),
                     info={"features": feats})

    def feature_columns(self, cols: dict, arch: str) -> FeatureBatch | None:
        """Optional vectorized feature hook: per-parameter *value* column
        arrays in, :class:`FeatureBatch` out — no per-config
        :class:`KernelFeatures` objects, no dicts.  The column math must
        mirror :meth:`features` operation for operation so the batched cost
        model produces bit-identical objectives (property-tested per
        kernel).  Return ``None`` to fall back to the per-config path.
        """
        return None

    def features_many(self, configs: Sequence[Config],
                      arch: str) -> FeatureBatch:
        """Struct-of-arrays features for a batch of *valid* configs.

        Routes through :meth:`feature_columns` when the problem provides it
        (columns are built once per parameter, not once per config);
        otherwise packs per-config :meth:`features` results into a
        :class:`FeatureBatch` in one pass.  The columnar path leaves
        ``FeatureBatch.features`` empty, in which case trials carry no
        per-config feature payload in ``info``.
        """
        if configs and \
                type(self).feature_columns is not TunableProblem.feature_columns:
            import numpy as np
            cols = {p.name: np.asarray([c[p.name] for c in configs])
                    for p in self.space.params}
            fb = self.feature_columns(cols, arch)
            if fb is not None:
                return fb
        return FeatureBatch.from_features(
            [self.features(c, arch) for c in configs])

    def _columnar_ok(self, n_rows: int) -> bool:
        """Columnar evaluation pays ~45 numpy dispatches per *batch*; below
        ``_COLUMNAR_MIN`` rows the scalar feature math is strictly faster,
        so the row endpoints fall back (identical objectives either way)."""
        return (n_rows >= _COLUMNAR_MIN
                and self.space.compiled() is not None
                and type(self).evaluate is TunableProblem.evaluate
                and type(self).feature_columns
                is not TunableProblem.feature_columns)

    def objectives_for_rows(self, rows: Sequence[int],
                            arch: str = DEFAULT_ARCH):
        """Objective seconds for *valid* compiled-space rows, as a float64
        array — the fully array-native endpoint (``inf`` == invalid on this
        arch).  The row tell protocol needs nothing else: no ``Trial``, no
        config dicts, no per-config features.  Falls back through
        :meth:`trials_for_rows` when there is no columnar path.
        """
        import numpy as np
        rows = list(rows)
        if not rows:
            return np.empty(0, dtype=np.float64)
        if self._columnar_ok(len(rows)):
            comp = self.space.compiled()
            fb = self.feature_columns(comp.value_columns(rows), arch)
            if fb is not None:
                return np.ascontiguousarray(np.broadcast_to(
                    np.asarray(estimate_seconds_batch(fb, arch),
                               dtype=np.float64), (len(rows),)))
        return np.array([t.objective if t.ok else math.inf
                         for t in self.trials_for_rows(rows, arch)],
                        dtype=np.float64)

    def objectives_for_rows_archs(self, rows: Sequence[int],
                                  archs: Sequence[str]):
        """(len(archs), len(rows)) objective matrix — the four-generation
        recording protocol's fast path: the mixed-radix decode and the
        per-parameter value columns are built once and shared across
        architectures (they are arch-independent); only the feature/
        cost-model sweep runs per generation."""
        import numpy as np
        rows = list(rows)
        out = np.empty((len(archs), len(rows)), dtype=np.float64)
        if not rows:
            return out
        if self._columnar_ok(len(rows)):
            comp = self.space.compiled()
            with span("eval.features", cat="eval", n=len(rows),
                      archs=len(archs)):
                cols = comp.value_columns(rows)
                if self.arch_independent_features:
                    fbs = [self.feature_columns(cols, archs[0])] * len(archs)
                else:
                    fbs = [self.feature_columns(cols, a) for a in archs]
            if all(fb is not None for fb in fbs):
                with span("eval.estimate", cat="eval", n=len(rows),
                          archs=len(archs)):
                    for i, (fb, arch) in enumerate(zip(fbs, archs)):
                        out[i] = np.broadcast_to(
                            np.asarray(estimate_seconds_batch(fb, arch)),
                            (len(rows),))
                return out
        comp = self.space.compiled()
        if comp is not None \
                and type(self).evaluate is TunableProblem.evaluate:
            # small batch: decode once, scalar feature math per arch (once
            # overall when the features are arch-independent)
            cfgs = comp.decode_many(rows)
            if self.arch_independent_features:
                feats = [self.features(c, archs[0]) for c in cfgs]
                for i, arch in enumerate(archs):
                    out[i] = [estimate_seconds(f, arch) for f in feats]
            else:
                for i, arch in enumerate(archs):
                    out[i] = [estimate_seconds(self.features(c, arch), arch)
                              for c in cfgs]
            return out
        for i, arch in enumerate(archs):
            out[i] = self.objectives_for_rows(rows, arch)
        return out

    def trials_for_rows_archs(self, rows: Sequence[int],
                              archs: Sequence[str]) -> list[list["Trial"]]:
        """Per-arch lazy trials for *valid* compiled-space rows, one list per
        arch (aligned with ``archs``) — the arch-shared recording endpoint:
        one :meth:`objectives_for_rows_archs` sweep (decode + value columns
        built once, shared by every architecture), row-backed
        :class:`Trial` objects out, no config dicts anywhere."""
        rows = [int(r) for r in rows]
        objs = self.objectives_for_rows_archs(rows, archs)
        sp = self.space
        return [[Trial(None, float(o), a, valid=math.isfinite(float(o)),
                       row=r, space=sp)
                 for r, o in zip(rows, objs[i])]
                for i, a in enumerate(archs)]

    def trials_for_rows(self, rows: Sequence[int],
                        arch: str = DEFAULT_ARCH) -> list[Trial]:
        """Array-in/array-out evaluation of *valid* compiled-space rows —
        the index-native runners' fast path.

        Value columns come straight from the mixed-radix code matrix (no
        per-config dicts), features from :meth:`feature_columns`, seconds
        from the batched cost model; the one batched decode builds the
        ``Trial`` configs for the trace.  Constraint checking is skipped:
        callers pass mask-validated rows.  Falls back to
        :meth:`evaluate_many` whenever the space is uncompiled, the problem
        overrides :meth:`evaluate`, or there is no columnar feature path.
        """
        rows = list(rows)
        if not rows:
            return []
        comp = self.space.compiled()
        fb = None
        if self._columnar_ok(len(rows)):
            with span("eval.features", cat="eval", n=len(rows), arch=arch):
                fb = self.feature_columns(comp.value_columns(rows), arch)
        if fb is None:
            if comp is not None \
                    and type(self).evaluate is TunableProblem.evaluate:
                # small batch: rows are pre-validated, so skip ``satisfies``
                # and run the scalar feature math straight
                out = []
                for r, c in zip(rows, comp.decode_many(rows)):
                    feats = self.features(c, arch)
                    t = estimate_seconds(feats, arch)
                    out.append(Trial(c, t, arch, valid=math.isfinite(t),
                                     info={"features": feats},
                                     row=r, space=self.space))
                return out
            if comp is not None:
                cfgs = comp.decode_many(rows)
            else:
                cfgs = [self.space.from_flat_index(int(r)) for r in rows]
            return self.evaluate_many(cfgs, arch)
        import numpy as np
        with span("eval.estimate", cat="eval", n=len(rows), arch=arch):
            times = np.broadcast_to(
                np.asarray(estimate_seconds_batch(fb, arch),
                           dtype=np.float64), (len(rows),))
        # lazy trials: the trace keeps only (row, objective); the config
        # dict materializes on first access (or via materialize_configs)
        sp = self.space
        out = []
        for r, t in zip(rows, times):
            t = float(t)
            out.append(Trial(None, t, arch, valid=math.isfinite(t),
                             row=r, space=sp))
        return out

    # -- convenience ------------------------------------------------------ #
    def evaluate_many(self, configs: Sequence[Config],
                      arch: str = DEFAULT_ARCH) -> list[Trial]:
        """Evaluate a batch of configs.

        Problems on the analytical path (``features`` + the TPU cost model)
        take a vectorized fast path: one numpy sweep over the whole batch
        via :meth:`features_many` + :func:`estimate_seconds_batch`.
        Subclasses that override :meth:`evaluate` (measured problems,
        function problems) fall back to the per-config loop.
        """
        configs = list(configs)
        if type(self).evaluate is not TunableProblem.evaluate:
            return [self.evaluate(c, arch) for c in configs]
        trials: list[Trial | None] = []
        slots: list[int] = []
        for cfg in configs:
            if not self.space.satisfies(cfg):
                trials.append(Trial(cfg, math.inf, arch, valid=False,
                                    info={"violated": self.space.violated(cfg)}))
            else:
                slots.append(len(trials))
                trials.append(None)
        if slots:
            import numpy as np
            batch = self.features_many([configs[j] for j in slots], arch)
            times = np.broadcast_to(
                np.asarray(estimate_seconds_batch(batch, arch),
                           dtype=np.float64), (len(slots),))
            per_row = batch.features or None
            for i, j in enumerate(slots):
                t = float(times[i])
                info = {"features": per_row[i]} if per_row else {}
                trials[j] = Trial(configs[j], t, arch,
                                  valid=math.isfinite(t), info=info)
        return trials  # type: ignore[return-value]

    def exhaustive(self, arch: str = DEFAULT_ARCH,
                   limit: int | None = None) -> list[Trial]:
        """Evaluate the whole constrained space (vectorized: compiled
        enumeration feeding the batched cost-model path).

        ``limit`` slices the compiled valid-row enumeration directly when a
        table exists (``valid_rows`` order == ``enumerate`` order, so the
        configs are identical to the Python iterator's first ``limit``);
        the iterator runs only for uncompiled spaces."""
        comp = self.space.compiled()
        if limit is None:
            cfgs = self.space.valid_configs()
        elif comp is not None:
            cfgs = comp.decode_many(comp.valid_rows[:limit])
        else:
            import itertools
            cfgs = list(itertools.islice(
                self.space.enumerate(constrained=True), limit))
        return self.evaluate_many(cfgs, arch)

    def sampled(self, n: int, seed: int = 0,
                arch: str = DEFAULT_ARCH) -> list[Trial]:
        """The paper's 10 000-random-configs protocol."""
        return self.evaluate_many(self.space.sample_distinct(n, seed), arch)

    def archs(self) -> tuple[str, ...]:
        return ARCH_NAMES


class FunctionProblem(TunableProblem):
    """Wrap a plain ``fn(config, arch) -> float`` as a problem (tests/toys)."""

    def __init__(self, space: SearchSpace,
                 fn: Callable[[Config, str], float], name: str = "fn"):
        super().__init__(space)
        self.fn = fn
        self.name = name

    def evaluate(self, config: Config, arch: str = DEFAULT_ARCH) -> Trial:
        if not self.space.satisfies(config):
            return Trial(config, math.inf, arch, valid=False)
        v = float(self.fn(config, arch))
        return Trial(config, v, arch, valid=math.isfinite(v))


class MeasuredProblem(TunableProblem):
    """Wall-clock measurement, on the device, of a callable built from a
    config.

    ``build(config)`` compiles ahead of time (``jax.jit(...).lower(...)
    .compile()``) and returns a zero-argument callable that runs the
    compiled program; ``KernelProblem.measured`` provides one for every
    kernel.  A build that raises — the chip's compiler refusing the config
    — is one invalid trial carrying the error, never a retry.  The
    objective is the best of ``repeats`` timings, each ending in
    ``jax.block_until_ready`` so that it covers the device's work and not
    only the enqueue.  Analytical studies use the cost model instead
    (deterministic, full-space-enumerable).
    """

    def __init__(self, space: SearchSpace,
                 build: Callable[[Config], Callable[[], Any]],
                 name: str = "measured", repeats: int = 5, warmup: int = 2):
        super().__init__(space)
        self.build = build
        self.name = name
        self.repeats = repeats
        self.warmup = warmup

    def evaluate(self, config: Config, arch: str = DEFAULT_ARCH) -> Trial:
        import jax
        if not self.space.satisfies(config):
            return Trial(config, math.inf, arch, valid=False)
        # the compile-vs-measure split: one span per phase so a trace
        # shows where a measured config's wall-clock went; both carry the
        # config's key, which ties them together in a trace.  Span overhead
        # sits outside the per-repeat perf_counter windows, so enabling
        # tracing cannot bias the recorded objective.
        key = "/".join(f"{k}={config[k]}" for k in sorted(config))
        try:
            with span("kernel.build", cat="kernel", arch=arch, key=key):
                fn = self.build(config)
        except Exception as e:  # config that fails to compile == invalid
            return Trial(config, math.inf, arch, valid=False,
                         info={"error": repr(e)})
        with span("kernel.measure", cat="kernel", arch=arch, key=key,
                  repeats=self.repeats,
                  calls=self.warmup + self.repeats) as s:
            for _ in range(self.warmup):
                jax.block_until_ready(fn())
            best = math.inf
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                best = min(best, time.perf_counter() - t0)
            s.set(best_s=best)
        return Trial(config, best, arch, valid=True)
