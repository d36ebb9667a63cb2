#!/usr/bin/env python3
"""Records ``data/v5e_scoped.xplane.pb`` on one TPU: a scale-8 Kronecker
graph (the ``kron-s20`` configuration cut to 256 vertices, plans
chunked/native as the autotuner picks them at scale 20), one BFS call and
one PageRank call inside a ``bench.window`` span, each in a ``bench.call``
span, with a garbage collection between them.

    python bench/tests/record_scoped_fixture.py OUT.xplane.pb

Both programs are compiled and run once before the profiler starts.  The
``/host:metadata`` plane (the programs' HLO) is left out of the file.
"""
from __future__ import annotations

import gc
import glob
import json
import os
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench import graphs, scopes
    from repro.sparse import CSR, Graph, bfs, build_advance, pagerank

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    cfg = json.loads((ROOT / "bench/configs/kron-s20.json").read_text())
    cfg.pop("undirected_edges")
    cfg["scale"] = 8
    offsets, cols, salt = graphs.generate(cfg, cfg["graph_seed"])
    n = offsets.size - 1
    o, c = jnp.asarray(offsets), jnp.asarray(cols)
    graph = Graph(CSR(o, c, graphs.edge_weights(o, c, salt), (n, n),
                      cols.size))
    plans = {w: build_advance(graph, schedule="chunked", path="native",
                              num_blocks=8, compact=True, workload=w)
             for w in ("advance", "reduce")}
    root = int(jnp.argmax(graph.out_degrees()))

    def calls():
        with jax.profiler.TraceAnnotation("bench.call"):
            bfs(graph, root, plan=plans["advance"]).block_until_ready()
        gc.collect()
        with jax.profiler.TraceAnnotation("bench.call"):
            pagerank(graph, plan=plans["reduce"],
                     num_iters=3).block_until_ready()

    calls()
    logdir = tempfile.mkdtemp(prefix="scoped-fixture-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=options):
        with jax.profiler.TraceAnnotation("bench.window"):
            calls()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    space = scopes._xspace_class().FromString(pathlib.Path(path).read_bytes())
    for plane in list(space.planes):
        if plane.name == "/host:metadata":
            space.planes.remove(plane)
    pathlib.Path(out).write_bytes(space.SerializeToString())
    print(json.dumps(scopes.summary(scopes.reduce_trace(out))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
