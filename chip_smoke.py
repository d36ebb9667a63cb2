#!/usr/bin/env python3
"""Smoke run of the graph main path on a TPU: proves the program starts there.

Generates a Graph500 Kronecker graph from ``--seed`` (scale 20 by default:
2**20 vertices, edge factor 16, initiator 0.57/0.19/0.19/0.05, a random
vertex permutation, symmetrised, deduplicated, no self-loops, weights
uniform in [0, 1)), then drives it through the entry points a user calls:

* ``build_advance(schedule="auto")``, then ``bfs`` from 4 roots,
  ``sssp(algorithm="delta")`` from one and ``pagerank`` for 20 iterations;
* a ``GraphServer`` with 8 lanes answering 16 mixed queries submitted
  between ticks;
* ``spmv_merge_path`` over the same CSR, once on the merge-stream kernel
  and once on the chunk-walking kernel (``schedule="auto"``).

Every answer is checked against a plain host reference that shares no code
with the package (SciPy's csgraph and sparse products, a NumPy power
iteration).  ``--chips 4`` runs only the mesh-sharded traversal instead and
compares it with the one-chip drivers on the same graph.

The script uses one process and starts none.  It fails unless JAX's first
device is a TPU.  The last line of its output is one JSON object naming the
device; the timings on earlier lines are those of a smoke run, not a
benchmark.

    python chip_smoke.py [--scale 20] [--seed 0]
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

EDGE_FACTOR = 16
INITIATOR = (0.57, 0.19, 0.19)        # A, B, C; D = 1 - A - B - C
NUM_ROOTS = 4
PAGERANK_ITERS = 20
SERVER_LANES = 8
SERVER_QUERIES = 16
ATOMS_PER_CHUNK = 2048                # sizes num_blocks from the edge count


def log(*parts) -> None:
    print("smoke", *parts, flush=True)


class Timer:
    """Wall-clock seconds of a block, printed with its label."""

    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"{self.label}_s={self.seconds:.3f}")


def kronecker_edges(scale: int, seed: int):
    """Graph500 Kronecker graph as a symmetric CSR (NumPy, host).

    Returns ``(row_offsets int32 [V+1], col_indices int32 [E], weights f32
    [E])`` with both directions of every undirected edge sharing one weight.
    """
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, EDGE_FACTOR << scale
    a, b, c = INITIATOR
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    w = rng.random(key.size, dtype=np.float32)
    rows = np.concatenate([lo, hi])
    cols = np.concatenate([hi, lo])
    order = np.lexsort((cols, rows))
    rows, cols, w = rows[order], cols[order], np.concatenate([w, w])[order]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=offsets[1:])
    return offsets.astype(np.int32), cols.astype(np.int32), w


class Reference:
    """Host answers from SciPy / NumPy for the same graph."""

    def __init__(self, offsets, cols, weights):
        import scipy.sparse as sp
        n = offsets.size - 1
        self.n = n
        self.weighted = sp.csr_matrix(
            (weights.astype(np.float64), cols, offsets), shape=(n, n))
        self.pattern = sp.csr_matrix(
            (np.ones(cols.size), cols, offsets), shape=(n, n))
        self.out_degree = np.diff(offsets)

    def bfs_levels(self, roots) -> np.ndarray:
        from scipy.sparse.csgraph import dijkstra
        hops = dijkstra(self.pattern, indices=list(roots), unweighted=True)
        return np.where(np.isfinite(hops), hops, -1).astype(np.int64)

    def sssp(self, roots) -> np.ndarray:
        from scipy.sparse.csgraph import dijkstra
        return dijkstra(self.weighted, indices=list(roots))

    def pagerank(self, iters: int, damping: float = 0.85) -> np.ndarray:
        deg = self.out_degree.astype(np.float64)
        pr = np.full(self.n, 1.0 / self.n)
        for _ in range(iters):
            share = np.where(deg > 0, pr / np.maximum(deg, 1.0), 0.0)
            dangling = pr[deg == 0].sum()
            pr = (1.0 - damping) / self.n + damping * (
                self.pattern.T @ share + dangling / self.n)
        return pr

    def spmv(self, x) -> np.ndarray:
        return self.weighted @ x.astype(np.float64)


def check_bfs(got, want, what: str) -> None:
    got = np.asarray(got).astype(np.int64)
    bad = np.flatnonzero(got != want)
    if bad.size:
        raise AssertionError(f"{what}: {bad.size} depths differ, first at "
                             f"{bad[:5].tolist()}: {got[bad[:5]].tolist()} "
                             f"!= {want[bad[:5]].tolist()}")


def check_close(got, want, what: str, *, rtol: float, atol: float) -> None:
    got = np.asarray(got, np.float64)
    if not np.array_equal(np.isfinite(got), np.isfinite(want)):
        raise AssertionError(f"{what}: reachability differs")
    fin = np.isfinite(want)
    err = np.abs(got[fin] - want[fin])
    lim = atol + rtol * np.abs(want[fin])
    if (err > lim).any():
        i = int(np.argmax(err - lim))
        raise AssertionError(f"{what}: max excess error at {i}: "
                             f"{got[fin][i]!r} vs {want[fin][i]!r}")
    log(f"{what}_max_abs_err={float(err.max(initial=0.0))!r}")


def timed(label: str, fn, *, calls: int = 2):
    """Run ``fn`` ``calls`` times (the first call compiles); returns the
    last result."""
    import jax
    for name in ("first", "second")[:calls]:
        with Timer(f"{label}_{name}"):
            out = jax.block_until_ready(fn())
    return out


def build_graph(scale: int, seed: int):
    import jax.numpy as jnp
    from repro.sparse import CSR, Graph

    with Timer("setup_generate"):
        offsets, cols, weights = kronecker_edges(scale, seed)
    n, e = offsets.size - 1, cols.size
    log(f"graph scale={scale} vertices={n} directed_edges={e} "
        f"max_degree={int(np.diff(offsets).max())}")
    with Timer("setup_to_device"):
        csr = CSR(jnp.asarray(offsets), jnp.asarray(cols),
                  jnp.asarray(weights), (n, n), int(e))
        graph = Graph(csr)
        graph.csr.values.block_until_ready()
    with Timer("setup_reference"):
        ref = Reference(offsets, cols, weights)
    rng = np.random.default_rng(seed + 1)
    roots = rng.choice(np.flatnonzero(np.diff(offsets) > 0), NUM_ROOTS,
                       replace=False)
    log(f"roots={roots.tolist()}")
    return graph, ref, [int(r) for r in roots]


def describe_plan(plan) -> None:
    for name, part, sched, path in (
            ("pull", plan.part, plan.schedule, plan.path),
            ("push", plan.push_part, plan.push_schedule, plan.push_path)):
        log(f"plan {name} schedule={sched.value} path={path.value} "
            f"chunks={part.num_blocks} "
            f"queues={part.num_physical_blocks or part.num_blocks} "
            f"atom_span={part.atom_span} tile_span={part.tile_span}")
    log(f"plan direction_threshold={plan.direction_threshold!r} "
        f"delta={plan.delta!r} compact_capacity={plan.compact_capacity}")


def num_blocks_for(graph) -> int:
    return max(graph.num_edges // (4 * ATOMS_PER_CHUNK), 32)


def one_chip(scale: int, seed: int) -> None:
    """The main path on one device, every result against the host."""
    import jax
    from repro.core.autotune import select_plan
    from repro.kernels.spmv_merge.ops import spmv_merge_path
    from repro.sparse import bfs, build_advance, pagerank, sssp

    graph, ref, roots = build_graph(scale, seed)
    nb = num_blocks_for(graph)
    with Timer("setup_plan"):
        plan = build_advance(graph, schedule="auto", num_blocks=nb,
                             delta="auto", compact=True)
        jax.block_until_ready(plan.out_degrees)
    log(f"plan num_blocks={nb}")
    describe_plan(plan)

    with Timer("setup_reference_bfs"):
        levels = ref.bfs_levels(roots)
    for k, root in enumerate(roots):
        depth = timed(f"bfs_root{k}", lambda: bfs(graph, root, plan=plan),
                      calls=2 if k == 0 else 1)
        check_bfs(depth, levels[k], f"bfs_root{k}")
    log("bfs=ok")

    with Timer("setup_reference_sssp"):
        dist_ref = ref.sssp(roots[:2])
    dist = timed("sssp_delta", lambda: sssp(
        graph, roots[0], plan=plan, algorithm="delta"))
    check_close(dist, dist_ref[0], "sssp_delta", rtol=1e-5, atol=1e-6)
    log("sssp_delta=ok")

    with Timer("setup_reference_pagerank"):
        pr_ref = ref.pagerank(PAGERANK_ITERS)
    pr = timed("pagerank", lambda: pagerank(
        graph, plan=plan, num_iters=PAGERANK_ITERS))
    check_close(pr, pr_ref, "pagerank", rtol=1e-4, atol=1e-10)
    log("pagerank=ok")

    serve(graph, plan, roots, levels, dist_ref, pr_ref)

    x = np.random.default_rng(seed + 2).random(graph.num_vertices,
                                               dtype=np.float32)
    y_ref = ref.spmv(x)
    xj = jax.numpy.asarray(x)
    y = timed("spmv_merge_stream", lambda: spmv_merge_path(graph.csr, xj))
    check_close(y, y_ref, "spmv_merge_stream", rtol=1e-4, atol=1e-5)
    spmv_plan = select_plan(graph.csr.workspec(), nb)
    log(f"spmv auto schedule={spmv_plan.schedule.value} "
        f"path={spmv_plan.path.value}")
    y = timed("spmv_chunk_walk", lambda: spmv_merge_path(
        graph.csr, xj, schedule="auto", num_blocks=nb))
    check_close(y, y_ref, "spmv_chunk_walk", rtol=1e-4, atol=1e-5)
    log("spmv=ok")


def serve(graph, plan, roots, levels, dist_ref, pr_ref) -> None:
    """16 mixed queries through 8 lanes, submitted between ticks."""
    from repro.serve.graph import GraphServer

    kinds = ("bfs", "sssp", "pagerank", "bfs")
    queries = []
    for q in range(SERVER_QUERIES):
        kind = kinds[q % len(kinds)]
        queries.append((kind, roots[q % NUM_ROOTS] if kind == "bfs"
                        else roots[q % 2]))
    with Timer("serve_construct"):
        server = GraphServer(graph, lanes=SERVER_LANES, plan=plan,
                             num_iters=PAGERANK_ITERS)
    results, pending, ticks = {}, list(queries), 0
    t0 = time.perf_counter()
    while pending or server.queued or server.in_flight:
        # two arrivals per serving slot: later queries land mid-flight
        for kind, source in pending[:2]:
            qid = server.submit(kind, source)
            results[qid] = None
        pending = pending[2:]
        for r in server.tick():
            results[r.qid] = r
        ticks += 1
        if ticks == 1 or ticks % 10 == 0:
            log(f"serve_tick={ticks} in_flight={server.in_flight} "
                f"elapsed_s={time.perf_counter() - t0:.3f}")
    log(f"serve_queries={len(results)} ticks={ticks} "
        f"steps={server.steps} total_s={time.perf_counter() - t0:.3f} "
        f"step_traces={server.step_traces}")
    if len(results) != SERVER_QUERIES or None in results.values():
        raise AssertionError("server lost a query")
    for qid, r in sorted(results.items()):
        if r.kind == "bfs":
            check_bfs(r.value, levels[roots.index(r.source)],
                      f"serve_q{qid}_bfs")
        elif r.kind == "sssp":
            check_close(r.value, dist_ref[roots.index(r.source)],
                        f"serve_q{qid}_sssp", rtol=1e-5, atol=1e-6)
        else:
            check_close(r.value, pr_ref, f"serve_q{qid}_pagerank",
                        rtol=1e-4, atol=1e-10)
    log("serve=ok")


def four_chips(scale: int, seed: int, num_shards: int = 4) -> None:
    """Mesh-sharded traversal against the one-device drivers, bitwise."""
    import jax
    from repro.sparse import (bfs, build_advance, build_sharded_advance,
                              delta_stepping, pagerank, sharded_bfs,
                              sharded_delta_stepping, sharded_pagerank)

    graph, ref, roots = build_graph(scale, seed)
    nb = num_blocks_for(graph)
    # the chunked work queue, which the one-chip autotuner picks for both
    # directions of a Graph500 graph; naming it skips the autotuner here
    with Timer("setup_plan"):
        plan = build_advance(graph, schedule="chunked", num_blocks=nb,
                             delta="auto", compact=True)
    with Timer("setup_sharded_plan"):
        splan = build_sharded_advance(graph, num_shards,
                                      schedule="chunked",
                                      shard_schedule="equal_width",
                                      num_blocks=max(nb // num_shards, 8),
                                      delta="auto", compact=True)
    describe_plan(splan.template)
    per_device = {}
    for leaf in jax.tree_util.tree_leaves(splan.data()):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            per_device[key] = per_device.get(key, 0) + shard.data.nbytes
    for dev, nbytes in sorted(per_device.items()):
        log(f"sharded_plan_bytes device={dev} bytes={nbytes}")
    if len(per_device) < num_shards:
        raise AssertionError(f"sharded plan lives on {len(per_device)} "
                             f"devices, expected {num_shards}")

    levels = ref.bfs_levels(roots)
    for k, root in enumerate(roots):
        got = timed(f"sharded_bfs_root{k}",
                    lambda: sharded_bfs(splan, root), calls=1)
        want = timed(f"bfs_root{k}", lambda: bfs(graph, root, plan=plan),
                     calls=1)
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise AssertionError(f"sharded_bfs_root{k} != bfs")
        check_bfs(got, levels[k], f"sharded_bfs_root{k}")
    log("sharded_bfs=bitwise_ok")

    got = timed("sharded_delta_stepping",
                lambda: sharded_delta_stepping(splan, roots[0]), calls=1)
    want = timed("delta_stepping", lambda: delta_stepping(
        graph, roots[0], plan=plan), calls=1)
    if not np.array_equal(np.asarray(got), np.asarray(want)):
        raise AssertionError("sharded_delta_stepping != delta_stepping")
    log("sharded_delta_stepping=bitwise_ok")

    got = timed("sharded_pagerank", lambda: sharded_pagerank(
        splan, num_iters=PAGERANK_ITERS), calls=1)
    want = timed("pagerank", lambda: pagerank(
        graph, plan=plan, num_iters=PAGERANK_ITERS), calls=1)
    check_close(got, np.asarray(want, np.float64), "sharded_pagerank",
                rtol=1e-5, atol=1e-10)
    log("sharded_pagerank=ok")


def devices_for(chips: int) -> dict:
    """Check the devices JAX sees; returns their summary for the last line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, "
                         f"found {len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def run(scale: int, seed: int, chips: int) -> None:
    """The one-chip phases, or with ``chips > 1`` the mesh phases.

    Runs on whatever devices JAX has; :func:`main` is what insists on a
    TPU.
    """
    import jax

    devices = jax.devices()
    log(f"jax={jax.__version__} platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} devices={len(devices)}")
    if chips == 1:
        one_chip(scale, seed)
    else:
        four_chips(scale, seed, chips)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=int, default=20,
                    help="Graph500 scale: 2**scale vertices (default 20)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the mesh-sharded traversal")
    args = ap.parse_args(argv)
    if args.scale < 1:
        ap.error("--scale must be positive")
    device = devices_for(args.chips)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    run(args.scale, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
