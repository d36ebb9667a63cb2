"""Report-only CLI: re-fit cost-model coefficients from measured records.

Closes the measured-cost feedback loop end to end (ROADMAP item 3's last
mile): run the autotuner in measured mode over a workload sweep so its
cache accumulates v2 records (median wall-times *plus* the model-feature
decomposition of each measured plan), then least-squares re-fit the
tunable :mod:`repro.core.balance` coefficients against those measurements
via :func:`repro.core.balance.fit_coefficients` and print the report.

**Report-only by design**: the tool never rewrites ``balance.py``.  The
fitted values describe the device the measurements ran on (on the CPU the
kernels run in the Pallas interpreter, which says nothing about a TPU) —
the printed table is for a human to read next to ``docs/autotune.md``
before deciding whether any hand-set constant deserves to move.

Usage::

    PYTHONPATH=src python benchmarks/fit_cost_model.py --smoke
    PYTHONPATH=src python benchmarks/fit_cost_model.py --cache /tmp/c.json
    PYTHONPATH=src python benchmarks/fit_cost_model.py \
        --cache /tmp/c.json --fit-only   # no new measurements

``--fit-only`` skips the measuring sweep and fits from whatever v2
records the cache already holds (e.g. one populated by a previous run or
by ``REPRO_AUTOTUNE_MEASURE=1`` production runs).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (Plan, WorkSpec, collect_fit_samples,
                        execute_scatter_reduce, execute_tile_reduce,
                        fit_coefficients, make_partition, select_plan,
                        time_fn)
from repro.core.autotune import AutotuneCache

NUM_BLOCKS = 64


def _workloads(smoke: bool):
    """(name, spec, out_ids, num_out, values) tuples for the measuring sweep."""
    from repro.sparse import random_csr, suite_like_corpus
    out = []
    for name, A in suite_like_corpus(smoke=True):
        out.append((f"corpus/{name}", A))
    if not smoke:
        out.append(("synthetic/powerlaw_skew1.4",
                    random_csr(2_000, 2_000, 50_000, skew=1.4,
                               empty_frac=0.1, seed=11)))
        out.append(("synthetic/scalefree",
                    random_csr(4_000, 4_000, 60_000, skew=1.3,
                               empty_frac=0.3, seed=13)))
    rows = []
    for name, A in out:
        spec = A.workspec()
        rows.append((name, spec, A.col_indices, int(A.shape[1]), A.values))
    return rows


def _measure_reduce(spec: WorkSpec, vals: jax.Array):
    """Timing closure for the reduce family: one plan -> median us."""
    def run(plan: Plan) -> float:
        part = make_partition(spec, plan.schedule, NUM_BLOCKS)

        @jax.jit
        def f(v):
            return execute_tile_reduce(spec, part, lambda a: v[a],
                                       path=plan.path)

        return time_fn(f, vals, warmup=1, iters=3)
    return run


def _measure_push(spec: WorkSpec, vals: jax.Array, out_ids: jax.Array,
                  num_out: int, mask: jax.Array):
    """Timing closure for the push-advance family (scatter-reduce)."""
    def run(plan: Plan) -> float:
        part = make_partition(spec, plan.schedule, NUM_BLOCKS)

        @jax.jit
        def f(v):
            return execute_scatter_reduce(spec, part, lambda a: v[a],
                                          out_ids, num_out,
                                          path=plan.path, atom_mask=mask)

        return time_fn(f, vals, warmup=1, iters=3)
    return run


def populate(cache: AutotuneCache, smoke: bool) -> int:
    """Measured-mode sweep: reduce + push-advance per workload."""
    # the sweep *is* the measured mode — force the gate on for this process
    os.environ["REPRO_AUTOTUNE_MEASURE"] = "1"
    rng = np.random.default_rng(5)
    n = 0
    for name, spec, out_ids, num_out, vals in _workloads(smoke):
        select_plan(spec, NUM_BLOCKS, cache=cache,
                    measure=_measure_reduce(spec, vals))
        mask = jnp.asarray(rng.random(spec.num_atoms) < 0.4)
        select_plan(spec, NUM_BLOCKS, cache=cache, workload="advance_push",
                    measure=_measure_push(spec, vals, out_ids, num_out, mask))
        n += 2
        print(f"  measured {name}: reduce + advance_push", flush=True)
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache", default="/tmp/repro_fit_cache.json",
                    help="autotune cache JSON accumulating v2 records")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus only (CI liveness)")
    ap.add_argument("--fit-only", action="store_true",
                    help="skip measuring; fit from existing cache records")
    ap.add_argument("--fresh", action="store_true",
                    help="clear the cache before measuring")
    args = ap.parse_args(argv)

    cache = AutotuneCache(args.cache)
    if args.fresh and not args.fit_only:
        cache.clear()
    if not args.fit_only:
        print(f"[fit_cost_model] measuring sweep -> {args.cache}")
        populate(cache, smoke=args.smoke)

    samples = collect_fit_samples(cache)
    print(f"[fit_cost_model] {len(samples)} fit samples in {args.cache}")
    if not samples:
        print("no measured records with stored features; run without "
              "--fit-only (or point --cache at a measured-mode cache)")
        return 1
    fit = fit_coefficients(samples)
    print(fit.report())
    print("FIT_OK" if fit.num_samples > 0 else "FIT_EMPTY")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
