"""Library-level wall-clock measurement for the measured-cost feedback loop.

The cost models in :mod:`repro.core.balance` are hand-set lockstep-step
counts; on hardware the model has never seen, the only ground truth is a
wall clock.  This module is the one place the repo times anything: the
benchmark harness (``benchmarks/_timing`` re-exports from here) and the
autotuner's measured mode (:func:`repro.core.autotune.select_plan` with
``measure=``) share the same helper, so every recorded microsecond obeys
the same warmup/median discipline and the same counter instrumentation.

The warmup contract
-------------------

``time_fn`` reports *steady-state* medians.  JAX callables pay their
tracing + compilation cost on the **first** call (and jitted callables may
re-trace on fresh shapes), so at least one warmup call is mandatory — it is
what isolates compile time from the steady state being measured.  Callers
passing an *unjitted* function still need the warmup: the first call
triggers any lazy constant foldings / op-by-op dispatch caches.  The
helper therefore **enforces** ``warmup >= 1`` and ``iters >= 1`` with a
clear error instead of silently returning a compile-polluted number (the
pre-PR-6 ``benchmarks/_timing.time_fn`` accepted ``warmup=0`` and would
happily report a median dominated by compilation).

Measurement counting
--------------------

Every ``time_fn`` call bumps the ``measurements`` counter of
:mod:`repro.core.telemetry`, which :func:`measurement_count` reads — the
regression hook tests use to assert the autotuner's persisted measurements
are *reused* on reload rather than re-taken (measuring is the expensive
step the v2 cache exists to amortize).
"""
from __future__ import annotations

import math
import time
from typing import Iterable

import jax

from repro.core import telemetry


def measurement_count() -> int:
    """Total ``time_fn`` invocations in this process (re-measurement hook)."""
    return telemetry.counters().get("measurements", 0)


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median steady-state wall-time (us) of a callable, blocked until ready.

    ``warmup`` calls run first and are discarded — they absorb trace +
    compile (see the module docstring's warmup contract; ``warmup >= 1``
    and ``iters >= 1`` are enforced).  The reported number is the median of
    ``iters`` timed calls, each blocked with ``jax.block_until_ready`` so
    async dispatch cannot leak work past the clock.
    """
    if warmup < 1:
        raise ValueError(
            f"time_fn needs warmup >= 1 (got {warmup}): the first call pays "
            f"trace/compile, which must not pollute the steady-state median")
    if iters < 1:
        raise ValueError(f"time_fn needs iters >= 1 (got {iters})")
    telemetry.count("measurements")
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


def geomean(xs: Iterable[float]) -> float:
    """Geometric mean of positive samples; empty input is a loud error.

    The benchmark summaries aggregate speedup *ratios*, where the geometric
    mean is the only mean that commutes with inversion.  An empty sweep is
    a harness bug (``exp(0/0)`` territory), not a statistic — raise rather
    than return garbage.  Values are floored at 1e-12 so a zero-time ratio
    degrades gracefully instead of taking ``log(0)``.
    """
    xs = list(xs)
    if not xs:
        raise ValueError("geomean of an empty sequence is undefined "
                         "(empty benchmark sweep?)")
    xs = [max(float(x), 1e-12) for x in xs]
    return math.exp(sum(math.log(x) for x in xs) / len(xs))
