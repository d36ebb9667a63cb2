"""Benchmark harness — one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV.  Roofline terms for the
(arch x shape) cells come from the dry-run (see EXPERIMENTS.md §Roofline),
not from CPU wall time.

``--smoke``: run every suite on one tiny shape and fail on any exception —
the CI guard against benchmark bit-rot (no timing signal, just liveness).
Smoke mode additionally:

* points every suite at **one shared autotune cache** (a fresh tempdir via
  ``REPRO_AUTOTUNE_CACHE``, unless the caller already pinned one), so
  suites stop re-running partition inspection per suite for recurring
  shapes, and
* prints per-suite and total **partition inspector counts**
  (``partition_builds=``) and fails if the total exceeds
  ``SMOKE_PARTITION_BUILD_CEILING`` — the regression hook for the PR-2
  re-inspection bug class (a cache regression shows up as a count
  explosion long before anyone reads a timing).

JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
import sys
import tempfile

#: Smoke-mode ceiling on total concrete partition builds across all suites.
#: Measured headroom: a healthy smoke run builds ~280 partitions
#: (cost-model scoring included); re-inspection regressions multiply that.
#: Raise this deliberately when a suite legitimately grows, never to
#: silence a jump.
SMOKE_PARTITION_BUILD_CEILING = 600


def main() -> None:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    args = [a for a in args if a != "--smoke"]
    only = args[0] if args else None
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache"))
    if smoke:
        # one shared cache dir for every suite (honoured lazily by
        # AutotuneCache, so setting it before the suite imports is enough)
        os.environ.setdefault("REPRO_AUTOTUNE_CACHE", os.path.join(
            tempfile.mkdtemp(prefix="repro_smoke_autotune_"),
            "autotune.json"))

    from benchmarks import (fig2_overhead, fig3_landscape, fig4_heuristic,
                            fig_dynamic, fig_graph, fig_serve,
                            fig_wavefront, moe_dispatch, packing_bench,
                            table1_loc)
    from repro.core import partition_build_count
    suites = [
        ("fig2_overhead", fig2_overhead),
        ("fig3_landscape", fig3_landscape),
        ("fig4_heuristic", fig4_heuristic),
        ("fig_dynamic", fig_dynamic),
        ("fig_graph", fig_graph),
        # fig_serve and fig_wavefront merge their sections into fig_graph's
        # JSON, so they must run after fig_graph in full runs
        ("fig_serve", fig_serve),
        ("fig_wavefront", fig_wavefront),
        ("table1_loc", table1_loc),
        ("moe_dispatch", moe_dispatch),
        ("packing_bench", packing_bench),
    ]
    rows = []
    failures = []
    builds_at_start = partition_build_count()
    print("name,us_per_call,derived")
    for name, mod in suites:
        if only and only not in name:
            continue
        start = len(rows)
        builds_before = partition_build_count()
        try:
            mod.run(rows, smoke=smoke)
        except Exception as exc:  # noqa: BLE001 - smoke mode reports & fails
            if not smoke:
                raise
            failures.append((name, exc))
            print(f"{name}/SMOKE_FAILED,0.0,{type(exc).__name__}: {exc}")
        if smoke:
            rows.append((f"{name}/inspector", 0.0,
                         f"partition_builds="
                         f"{partition_build_count() - builds_before}"))
        for r in rows[start:]:
            print(f"{r[0]},{r[1]:.1f},{r[2]}")
        sys.stdout.flush()
    if smoke:
        total_builds = partition_build_count() - builds_at_start
        over = total_builds > SMOKE_PARTITION_BUILD_CEILING
        print(f"smoke,0.0,suites_failed={len(failures)};"
              f"partition_builds_total={total_builds};"
              f"build_ceiling={SMOKE_PARTITION_BUILD_CEILING};"
              f"reinspection={'REGRESSED' if over else 'ok'}")
        if failures or over:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
