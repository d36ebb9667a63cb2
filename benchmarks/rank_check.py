"""Ordinal perf-ranking gate over a committed ``BENCH_graph.json``.

CI boxes are too noisy for wall-clock thresholds, but *rankings* are stable:
on a scale-free graph the chunked work queue beats every static schedule by
integer factors, and a direction-optimizing BFS beats pull-only whenever
sparse-frontier iterations exist — orderings that survive machine jitter
even when absolute microseconds do not.  This script asserts those ordinal
invariants against the committed benchmark JSON (refreshed by full
``fig_graph`` runs, uploaded fresh per CI run for trajectory grooming) and
exits non-zero on any violation, so a perf regression that flips an
ordering fails the ``bench-rank`` job without a single timing threshold.

Usage: ``python benchmarks/rank_check.py [BENCH_graph.json]``
"""
from __future__ import annotations

import json
import pathlib
import sys

STATIC_SCHEDULES = ("thread_mapped", "group_mapped", "nonzero_split",
                    "merge_path")

#: The scale-free corpus entry where the dynamic queue must stay on top.
QUEUE_WINS_ON = "corpus/scalefree_web"

#: Modeled-regret ceiling: "auto" must pick the modeled argmin (regret 1.0);
#: the epsilon only absorbs the JSON rounding.
MAX_AUTO_REGRET = 1.001

#: Degree-aware boundary floor: equal_width's best sweep point over
#: edge_balanced's best sweep point on the skewed corpus graph (each
#: schedule at its own best shard count).  Every point runs the identical
#: compiled program (only the boundary placement differs), so >= 1.0 is
#: the structural expectation on a hub-skewed graph; the floor sits just
#: below it to absorb min-of-5 timer noise on shared CI boxes.
EB_VS_EW_FLOOR = 0.95


def check(bench: dict) -> list:
    failures = []

    def ensure(cond: bool, msg: str) -> None:
        if not cond:
            failures.append(msg)

    # 1. dynamic-queue ranking: chunked beats every static schedule on the
    #    scale-free web graph (the Atos regime) in measured wall-clock.
    entry = bench.get(QUEUE_WINS_ON)
    ensure(entry is not None, f"missing benchmark entry: {QUEUE_WINS_ON}")
    if entry:
        us = entry["schedules_us"]
        for sched in STATIC_SCHEDULES:
            ensure(us["chunked"] < us[sched],
                   f"{QUEUE_WINS_ON}: chunked ({us['chunked']}us) no longer "
                   f"beats {sched} ({us.get(sched)}us)")

    # 2. autotuner regret: "auto" is the modeled argmin on every workload.
    for name, e in bench.items():
        if name.startswith("_"):
            continue
        ensure(e.get("auto_regret", 1.0) <= MAX_AUTO_REGRET,
               f"{name}: auto_regret {e.get('auto_regret')} > "
               f"{MAX_AUTO_REGRET}")

    # 2b. measured-cost feedback loop (PR 6): on every workload carrying
    #     both fields, the measured-mode choice's *measured* regret must
    #     not exceed the model-only choice's — measured mode saw every
    #     candidate's wall-clock, so ranking by it can only improve the
    #     pick.  Asserted hardest on the advance-family acceptance graph.
    for name, e in bench.items():
        if name.startswith("_") or "measured_mode_regret" not in e:
            continue
        ensure(e["measured_mode_regret"]
               <= e.get("model_only_regret_measured", float("inf")) + 1e-3,
               f"{name}: measured-mode regret {e['measured_mode_regret']} "
               f"worse than model-only "
               f"{e.get('model_only_regret_measured')}")
    entry_acc = bench.get(QUEUE_WINS_ON, {})
    ensure("measured_mode_regret" in entry_acc,
           f"{QUEUE_WINS_ON}: missing measured_mode_regret (measured-mode "
           f"autotuning never ran on the acceptance graph)")
    ensure(bench.get("_summary", {}).get("measured_loop") == "ok",
           f"measured-cost loop regressed: "
           f"{bench.get('_summary', {}).get('measured_loop')}")

    # 3. push-direction ranking: with a ~30%-active frontier the push
    #    scatter must not be slower than the pull tile-reduce under
    #    merge-path (pull pays the full local-binning contraction; push
    #    windows skip it) on the queue-wins graph.
    if entry and "schedules_push_us" in entry:
        ensure(entry["schedules_push_us"]["merge_path"]
               < entry["schedules_us"]["merge_path"],
               f"{QUEUE_WINS_ON}: push merge_path advance "
               f"({entry['schedules_push_us']['merge_path']}us) not faster "
               f"than pull ({entry['schedules_us']['merge_path']}us)")

    # 4. direction-optimizing BFS: beats pull-only on the power-law corpus
    #    graph, and both directions actually ran.
    d = bench.get("_bfs_direction")
    ensure(d is not None, "missing _bfs_direction entry")
    if d:
        ensure(d["direction_optimizing_us"] < d["pull_only_us"],
               f"direction-optimizing BFS ({d['direction_optimizing_us']}us)"
               f" not faster than pull-only ({d['pull_only_us']}us)")
        ensure(d["push_iters"] > 0, "direction sweep never ran push")
        ensure(d["pull_iters"] > 0, "direction sweep never ran pull")

    # 5. delta-stepping SSSP: the best bucket width is no slower than the
    #    frontier Bellman-Ford on the weighted scale-free corpus graph.
    #    Near-structural rather than strictly so: the Delta -> inf sweep
    #    point runs Bellman-Ford's exact advance sequence but pays small
    #    bucket bookkeeping on top, and the committed best (width = mean
    #    weight) wins by staying on sparse push frontiers (~1.7x in the
    #    committed run) — min-of-5 sweep sampling plus that margin is
    #    what absorbs refresh noise.  Width tuning is delta-stepping's
    #    own game (Meyer & Sanders' Delta is a free parameter).
    ds = bench.get("_sssp_delta")
    ensure(ds is not None, "missing _sssp_delta entry")
    if ds:
        ensure(ds["best_us"] <= ds["bellman_ford_us"],
               f"delta-stepping best ({ds['best_us']}us, width "
               f"{ds.get('best')}) slower than Bellman-Ford "
               f"({ds['bellman_ford_us']}us) on {ds.get('graph')}")
        ensure(len(ds.get("sweep_us", {})) >= 3,
               "delta-stepping width sweep too small")
        ensure(ds.get("compact_us", 0) > 0,
               "compacted-window delta ride-along missing")
        # the SSSP direction switch must actually fire: the best width's
        # sparse bucket frontiers run push phases (counts threaded
        # through the carry by sssp/delta_stepping's
        # return_direction_counts)
        best_advances = ds.get("advances", {}).get(ds.get("best"), [0, 0])
        ensure(best_advances[0] > 0,
               f"best-width delta-stepping never ran a push phase "
               f"({best_advances})")

    # 6. mesh-sharded BFS (PR 7): the 1-shard mesh must reproduce the
    #    unsharded driver bitwise (the recursion's base case — any halo or
    #    padding defect breaks it even on one device), and the measured
    #    count selection can never regret more than the model-only pick
    #    (same closed-loop argument as 2b: measured mode saw every
    #    candidate's wall-clock).  Shard *speedup* is recorded but not
    #    ranked — on a forced-host-device CPU harness the collective
    #    round-trips swamp the per-shard compute shrink; the speedup
    #    column is a real-hardware trajectory number.
    sh = bench.get("_sharded")
    ensure(sh is not None, "missing _sharded entry (mesh-sharded BFS "
                           "sweep never ran)")
    if sh:
        ensure(sh.get("one_shard_bitwise") is True,
               f"{sh.get('graph')}: 1-shard sharded BFS no longer "
               f"bitwise-identical to the unsharded driver")
        ensure(sh.get("sharded_auto_regret", float("inf"))
               <= sh.get("sharded_model_only_regret", 0.0) + 1e-3,
               f"{sh.get('graph')}: measured shard-count selection regret "
               f"{sh.get('sharded_auto_regret')} worse than model-only "
               f"{sh.get('sharded_model_only_regret')}")
        ensure(len(sh.get("sweep_us", {})) >= 1,
               "sharded sweep recorded no shard counts")
        ensure(len(sh.get("sweep_us", {})) >= len(sh.get("counts", [])),
               "sharded sweep dropped candidate counts")

    # 6b. boundary schedules (PR 10): the sweep must cover every
    #     registered boundary schedule (each bitwise-asserted inside
    #     fig_graph before timing), and on the skewed scale-free graph
    #     the degree-aware edge_balanced placement's best sweep point
    #     must be no slower than uniform equal_width's best sweep point.
    #     That head-to-head is near-structural: the two builds run the
    #     identical compiled program and collective sequence, differing
    #     only in where the contiguous boundaries land, so on a
    #     hub-skewed graph balancing edges can only shrink the max-shard
    #     work — EB_VS_EW_FLOOR (just under 1.0) is the min-of-5
    #     timer-noise allowance, same role as the 2b epsilon.
    if sh:
        bsweep = sh.get("boundary_sweep_us", {})
        for bname in sh.get("boundaries", []):
            ensure(len(bsweep.get(bname, {})) >= 1,
                   f"boundary sweep missing schedule {bname!r}")
        ensure(len(bsweep.get("equal_width", {}))
               >= len(sh.get("counts", [])),
               "equal_width boundary sweep dropped candidate counts")
        ratio = sh.get("edge_balanced_vs_equal_width")
        if sh.get("devices", 1) > 1:
            ensure(ratio is not None,
                   "multi-device sweep missing the edge_balanced vs "
                   "equal_width head-to-head")
        if ratio is not None:
            ensure(ratio >= EB_VS_EW_FLOOR,
                   f"{sh.get('graph')}: edge_balanced best point "
                   f"{ratio}x equal_width's best point "
                   f"({sh.get('equal_width_best')}) — degree-aware "
                   f"boundaries regressed below {EB_VS_EW_FLOOR}x")
        # joint (count, boundary) auto-selection must honour the same
        # measured-beats-model ordering checked in 6 — re-assert here so
        # a boundary-dimension regression names itself
        ensure(sh.get("sharded_auto_regret", float("inf"))
               <= sh.get("sharded_model_only_regret", 0.0) + 1e-3,
               f"{sh.get('graph')}: joint (count, boundary) measured "
               f"selection regret {sh.get('sharded_auto_regret')} worse "
               f"than model-only "
               f"{sh.get('sharded_model_only_regret')}")

    # 7. serving (PR 8): the whole stream must have been served on ONE
    #    trace of the step, tail latency must be reported, and the mixed
    #    BFS/SSSP/PageRank correctness phase must have stayed bitwise vs
    #    the drivers.  Queries/sec of the server and of the sequential and
    #    precompiled single-query paths are recorded but not ranked: the
    #    drivers compile their loops once per plan, and on the CPU the
    #    vmapped lanes run one after another, so which path wins is a
    #    question for a chip benchmark.
    sv = bench.get("_serving")
    ensure(sv is not None, "missing _serving entry (fig_serve never ran)")
    if sv:
        ensure(sv.get("p99_ms", 0) > 0, "serving p99 latency not reported")
        ensure(sv.get("p50_ms", 0) > 0, "serving p50 latency not reported")
        ensure(sv.get("step_traces") == 1,
               f"serving step traced {sv.get('step_traces')} times "
               f"(no-retrace contract broken)")
        ensure(sv.get("mixed_bitwise") is True,
               "served mixed-stream answers no longer bitwise-identical "
               "to the single-query drivers")

    # 8. wavefront DAG evaluation (PR 9): on the fan-in-skewed forest —
    #    one hub aggregator owns hundreds of dependency in-edges while
    #    chain nodes own one, exactly the skew the dynamic work queue
    #    exists for — the chunked combine must not be slower than the
    #    *worst* static schedule (weaker than the scale-free advance gate
    #    in section 1: the combine replays per feature column under vmap,
    #    which flattens some of the queue's win).  The level count pins
    #    the multi-level structure (a 1-level "DAG" would vacuously pass
    #    everything), and auto must still be the modeled argmin.  The
    #    sequential-oracle speedup is recorded, not ranked — a Python
    #    per-node loop is not a serious baseline, just the recursion the
    #    scheduler replaces.
    wf = bench.get("_wavefront")
    ensure(wf is not None, "missing _wavefront entry (fig_wavefront never "
                           "ran)")
    if wf:
        q = wf.get("graphs", {}).get(wf.get("queue_graph", ""), {})
        ensure(bool(q), f"missing wavefront queue graph entry "
                        f"{wf.get('queue_graph')}")
        if q:
            cu = q.get("combine_us", {})
            worst_static = max((cu.get(s, 0.0) for s in STATIC_SCHEDULES),
                              default=0.0)
            ensure(cu.get("chunked", float("inf")) <= worst_static,
                   f"{wf.get('queue_graph')}: chunked combine "
                   f"({cu.get('chunked')}us) slower than the worst static "
                   f"schedule ({worst_static}us)")
            ensure(q.get("levels", 0) >= 3,
                   f"wavefront queue graph has {q.get('levels')} levels "
                   f"(need >= 3 for a real multi-level gate)")
        for gname, e in wf.get("graphs", {}).items():
            ensure(e.get("auto_regret", 1.0) <= MAX_AUTO_REGRET,
                   f"wavefront/{gname}: auto_regret "
                   f"{e.get('auto_regret')} > {MAX_AUTO_REGRET}")
        ensure(wf.get("status") == "ok",
               f"wavefront gate not healthy: {wf.get('status')}")

    # 9. liveness markers recorded by the full run.
    summary = bench.get("_summary", {})
    ensure(summary.get("native_path") == "ok",
           f"native path not exercised: {summary.get('native_path')}")
    ensure(summary.get("direction_switch") == "ok",
           f"direction switch not exercised: "
           f"{summary.get('direction_switch')}")
    ensure(summary.get("delta_stepping") == "ok",
           f"delta-stepping not competitive: "
           f"{summary.get('delta_stepping')}")
    ensure(summary.get("sharded") == "ok",
           f"sharded sweep not healthy: {summary.get('sharded')}")
    ensure(summary.get("serving") == "ok",
           f"serving gate not healthy: {summary.get('serving')}")
    ensure(summary.get("wavefront") == "ok",
           f"wavefront gate not healthy: {summary.get('wavefront')}")
    ensure(bench.get("_bfs_batched", {}).get("sources", 0) > 1,
           "batched multi-source BFS sweep missing")
    return failures


def main() -> None:
    path = pathlib.Path(sys.argv[1] if len(sys.argv) > 1
                        else "BENCH_graph.json")
    bench = json.loads(path.read_text())
    failures = check(bench)
    for f in failures:
        print(f"RANK-CHECK FAIL: {f}")
    print(f"rank_check: {len(failures)} failures over "
          f"{sum(not k.startswith('_') for k in bench)} workloads "
          f"({path})")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
