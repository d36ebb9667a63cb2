"""Continuous-batching traversal serving: queries/sec + tail latency.

Measures the serving layer (``repro.serve.graph.GraphServer``) against the
single-query drivers on a mixed BFS/SSSP arrival stream:

* **batched**: all queries through one ``GraphServer`` — W lanes over one
  shared plan pair, retire-and-backfill, exactly one trace of the jitted
  serving step for the whole stream (asserted).
* **sequential**: the shipped single-query path — one driver call per
  query.  The drivers compile their loop once per plan and reuse it for
  every source.
* **sequential_precompiled**: a ``jax.jit`` wrapper per (kind, graph,
  plan) compiled once, then called per query.

The three rates are recorded, not rank-gated: on the CPU bench harness
vmapped lanes run one after another, so which path wins is a question for
a chip benchmark.

Latency percentiles (p50/p99, submit-to-retire, queueing included) come
from the per-query timestamps every ``ServedResult`` carries.

A correctness phase serves a small mixed stream *including PageRank* and
asserts every retired answer is bitwise-identical to its driver — the
serving acceptance contract, re-checked on the benchmark graph.

Results merge into ``BENCH_graph.json`` (never clobbering the fig_graph
entries) as a ``_serving`` section plus a ``serving`` marker in
``_summary``; ``rank_check.py`` gates on them.
"""
from __future__ import annotations

import json
import os
import pathlib
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.sparse import (CSR, Graph, bfs, pagerank, random_csr, sssp,
                          suite_like_corpus)
from repro.serve.graph import GraphServer

#: The serving acceptance graph — the power-law corpus entry the other
#: graph gates (direction switch, delta-stepping, sharding) target too.
SERVE_GRAPH = "corpus/scalefree_web"


def _as_graph(A: CSR) -> Graph:
    return Graph(CSR(A.row_offsets, A.col_indices,
                     jnp.abs(A.values) + 0.05, A.shape, A.nnz))


def _pick_graph(smoke: bool):
    if smoke:
        A = random_csr(120, 120, 700, skew=1.3, empty_frac=0.1, seed=17)
        return "powerlaw/powerlaw_small", _as_graph(A)
    fallback = None
    for cname, A in suite_like_corpus(smoke=False):
        rows, cols = A.shape
        if rows != cols or A.nnz == 0:
            continue
        if f"corpus/{cname}" == SERVE_GRAPH:
            return SERVE_GRAPH, _as_graph(A)
        if fallback is None and A.nnz <= 150_000:
            fallback = (f"corpus/{cname}", _as_graph(A))
    return fallback


def _stream_sources(g: Graph, n: int, target_deg: int = 8):
    """Deterministic medium-degree sources (hubs saturate in one step)."""
    outdeg = np.asarray(g.out_degrees())
    return [int(s) for s in np.argsort(np.abs(outdeg - target_deg))[:n]]


def _driver(kind: str):
    return {"bfs": bfs, "sssp": sssp, "pagerank": pagerank}[kind]


def _driver_answer(g, plan, kind, source):
    if kind == "pagerank":
        return np.asarray(pagerank(g, plan=plan, direction="pull"))
    return np.asarray(_driver(kind)(g, source, plan=plan, direction="pull"))


def run(csv_rows, smoke: bool = False):
    name, g = _pick_graph(smoke)
    lanes = 2 if smoke else 8
    n_queries = 4 if smoke else 16
    srv = GraphServer(g, lanes=lanes, direction="pull", schedule="auto")
    plan = srv.plan

    # -- correctness phase: mixed stream incl. PageRank, bitwise ---------
    sources = _stream_sources(g, max(n_queries, 4))
    mixed = [("bfs", sources[0]), ("sssp", sources[1]), ("pagerank", 0),
             ("bfs", sources[2])]
    qk = {}
    for kind, s in mixed:
        qk[srv.submit(kind, source=s)] = (kind, s)
    mixed_ok = True
    for r in srv.drain():
        kind, s = qk[r.qid]
        want = _driver_answer(g, plan, kind, s)
        got = np.asarray(r.value)
        if got.dtype != want.dtype or not np.array_equal(got, want):
            mixed_ok = False
    one_trace = srv.step_traces == 1 and srv.admit_traces == 1

    # -- throughput phase: BFS+SSSP stream, batched vs sequential --------
    queries = [("bfs" if i % 2 == 0 else "sssp", s)
               for i, s in enumerate(sources[:n_queries])]

    t0 = time.perf_counter()
    for kind, s in queries:
        srv.submit(kind, source=s)
    results = srv.drain()
    batched_s = time.perf_counter() - t0
    one_trace = one_trace and srv.step_traces == 1 and srv.admit_traces == 1
    lat_ms = sorted(r.latency * 1e3 for r in results)
    p50 = lat_ms[len(lat_ms) // 2]
    p99 = lat_ms[min(len(lat_ms) - 1, int(np.ceil(0.99 * len(lat_ms))) - 1)]

    # sequential: the shipped per-query path
    t0 = time.perf_counter()
    for kind, s in queries:
        jax.block_until_ready(
            _driver(kind)(g, s, plan=plan, direction="pull"))
    sequential_s = time.perf_counter() - t0

    # precompiled best-case: one jit per kind, compile outside the clock
    jitted = {k: jax.jit(lambda s, _k=k: _driver(_k)(g, s, plan=plan,
                                                     direction="pull"))
              for k in ("bfs", "sssp")}
    for k in jitted:
        jax.block_until_ready(jitted[k](jnp.int32(queries[0][1])))
    t0 = time.perf_counter()
    for kind, s in queries:
        jax.block_until_ready(jitted[kind](jnp.int32(s)))
    precompiled_s = time.perf_counter() - t0

    n = len(queries)
    serving = {
        "graph": name, "V": g.num_vertices, "E": g.num_edges,
        "lanes": lanes, "queries": n,
        "batched_qps": round(n / batched_s, 2),
        "sequential_qps": round(n / sequential_s, 2),
        "sequential_precompiled_qps": round(n / precompiled_s, 2),
        "p50_ms": round(p50, 2), "p99_ms": round(p99, 2),
        "step_traces": srv.step_traces, "admit_traces": srv.admit_traces,
        "mixed_bitwise": mixed_ok,
    }
    ok = mixed_ok and one_trace

    # merge (never clobber) into the fig_graph-owned JSON
    out_dir = os.environ.get("REPRO_BENCH_DIR")
    if out_dir or not smoke:
        path = pathlib.Path(out_dir or ".") / "BENCH_graph.json"
        try:
            bench = json.loads(path.read_text()) if path.exists() else {}
            bench["_serving"] = serving
            bench.setdefault("_summary", {})["serving"] = (
                "ok" if ok else "regressed")
            path.write_text(json.dumps(bench, indent=1))
        except OSError:
            pass   # read-only CWD: the CSV rows still carry the numbers

    csv_rows.append((
        f"fig_serve/{name}", round(batched_s * 1e6 / n, 1),
        f"serving={'ok' if ok else 'regressed'};"
        f"batched_qps={serving['batched_qps']};"
        f"sequential_qps={serving['sequential_qps']};"
        f"precompiled_qps={serving['sequential_precompiled_qps']};"
        f"p50_ms={serving['p50_ms']};p99_ms={serving['p99_ms']};"
        f"step_traces={srv.step_traces};"
        f"mixed_bitwise={mixed_ok}"))
