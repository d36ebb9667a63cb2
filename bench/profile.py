#!/usr/bin/env python3
"""One run of one benchmark cell under the profiler, reduced by the
program's own scopes and spans.

    python bench/profile.py --workload kron-s20.bfs --seed 7 --seconds 25

Runs the cell as ``bench/run.py --trace 0`` does (the same set-up, window
and check, the same caches) inside one profiler session, then prints one
JSON line: the run's result line, and under ``scoped`` the window's device
time by named scope, the program's spans in the window, the idle gaps
labelled with the innermost ``bench.`` or ``repro.`` span, and the
program's counters (``repro.core.telemetry``).  The result's numbers are
taken with the profiler on, so they say what tracing costs, not what the
untraced metrics are.  The session spans the whole run, set-up included,
so JAX's Python tracer is off: it would record every Python call of the
set-up.  ``--out DIR`` keeps the trace in ``DIR``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="directory that keeps the trace")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, scopes, trace
    from bench.run import configure
    from repro.core import telemetry

    cell = harness.find_cell(ROOT, args.workload)
    device = harness.require_chips(cell.chips)
    with tempfile.TemporaryDirectory(prefix="bench-") as scratch:
        configure(scratch)
        logdir = args.out or os.path.join(scratch, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with jax.profiler.trace(logdir, profiler_options=options):
            result = harness.run_cell(ROOT, args.workload, seed=args.seed,
                                      seconds=args.seconds, trace=False,
                                      t_start=T_START, device=device)
        result["scoped"] = {
            **scopes.summary(scopes.reduce_trace(trace.find_xplane(logdir))),
            "counters": telemetry.counters()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
