#!/usr/bin/env python3
"""The controls that the check of ``correct`` has to reject, per cell.

    python bench/control.py --workload kron-s20.pagerank --seeds 1 2 3

Sets the cell's graph up as a run does, puts a control in the program's
place, and compares its answers through the cell's own check.  Prints one
JSON line per seed: each number compared, with its limit.

* ``bfs``: the configuration states exact depths.  The control is the
  reference with its deepest level left out (a traversal that stops one
  level early).
* ``pagerank``: the configuration states float32 ranks.  The control is the
  reference computed in bfloat16 on the device, the next precision down.

Not part of a benchmark run; the readings set the upper end of each limit.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def bfs_control(ref, root: int) -> np.ndarray:
    depth = ref.bfs_depths(root)
    return np.where(depth == depth.max(), -1, depth)


def pagerank_control(offsets, cols, iters: int, damping: float,
                     dtype=None) -> np.ndarray:
    """PageRank as the reference computes it, every value in ``dtype``."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    n = offsets.shape[0] - 1
    deg = jnp.diff(jnp.asarray(offsets))
    rows = jnp.repeat(jnp.arange(n), deg, total_repeat_length=cols.shape[0])

    @jax.jit
    def run(cols, rows, deg):
        degf = deg.astype(dtype)
        d = jnp.asarray(damping, dtype)

        def step(_, pr):
            share = jnp.where(deg > 0, pr / jnp.maximum(degf, 1), 0)
            share = share.astype(dtype)
            dangling = jnp.sum(jnp.where(deg > 0, 0, pr).astype(dtype))
            contrib = jax.ops.segment_sum(share[cols], rows, n)
            return ((1 - d) / n + d * (contrib + dangling / n)).astype(dtype)

        return jax.lax.fori_loop(0, iters, step,
                                 jnp.full((n,), 1.0 / n, dtype))

    return np.asarray(run(jnp.asarray(cols), rows, deg).astype(jnp.float32))


def readings(root: pathlib.Path, name: str, seeds) -> list[dict]:
    from bench import graphs, harness
    from bench.reference import Reference

    cell = harness.find_cell(root, name)
    cfg, mix = cell.config, cell.mix
    offsets, cols, _ = graphs.generate(cfg, cfg["graph_seed"])
    ref = Reference(offsets, cols)
    degrees = np.diff(offsets)
    out = []
    for seed in seeds:
        traffic = harness.load_kind(root, mix["kind"])(mix, seed, degrees)
        if mix["kind"] == "bfs":
            traffic.host = [bfs_control(ref, r) for r in traffic.roots]
        else:
            traffic.host = [pagerank_control(
                offsets, cols, int(mix["num_iters"]), float(mix["damping"]))]
        compared, failed = traffic.check(ref)
        out.append({"seed": seed, "failed": failed,
                    "compared": {k: {"value": v, "limit": lim}
                                 for k, (v, lim) in compared.items()}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    harness.require_chips(harness.find_cell(ROOT, args.workload).chips)
    for line in readings(ROOT, args.workload, args.seeds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
