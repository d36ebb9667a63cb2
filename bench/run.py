#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload kron-s20.bfs --seed 7 --seconds 45 --trace 0

Sets the cell up (graph, plan, one warm-up call), runs whole calls back to
back for ``--seconds``, checks what the window produced against the plain
reference, and prints one JSON line: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics from a profiler trace of the window
with ``--trace 1``.  The numbers compared and their limits are the last
lines on standard error.  Exits non-zero, printing no result, when JAX's
first device is not a TPU or the cell asks for more chips than there are.

JAX's persistent compilation cache lives at a fixed path inside the
checkout, so only a checkout's first run compiles.  Plans are scored as a
new user's would be: an autotune cache of the run's own, empty at start,
and the cost model alone (no measured mode).
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
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def configure(scratch: str) -> None:
    """This process's compilation and autotune caches."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, the inspector's small ones included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(scratch, "autotune.json")
    os.environ.pop("REPRO_AUTOTUNE_MEASURE", None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    cell = harness.find_cell(ROOT, args.workload)
    device = harness.require_chips(cell.chips)
    with tempfile.TemporaryDirectory(prefix="bench-") as scratch:
        configure(scratch)
        result = harness.run_cell(ROOT, args.workload, seed=args.seed,
                                  seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START,
                                  device=device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
