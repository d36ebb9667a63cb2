"""Finds a cell's pieces by name and runs it: set-up, window, check.

Everything that belongs to one configuration, one traffic mix or one metric
sits in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``BENCHMARK.json``'s ``configs[].file``: the graph and the plan options;
* ``bench/traffic/<traffic>.json``: the mix, a data file that names its
  ``kind`` and holds the kind's parameters and the limits of its check;
* ``bench/traffic/kinds/<kind>.py``: the kind, a class ``Traffic`` with
  ``cycle``, ``warm_up``, ``call``, ``collect`` and ``check``;
* ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float | None``
  for each end-to-end and per-layer metric.

A cell's ``chips`` decide where its graph lives and which plan is built: on
one chip the graph goes to the default device and ``build_advance`` plans
it; on n chips the program gets the host arrays and ``build_sharded_advance``
plans them over its own graph mesh of n devices.  The kinds call the same
drivers, which route on the plan's type.

A run is a new process: its set-up is everything from the start of the
process to the first timed call, and nothing compiles inside the window.
The window runs the traffic's calls back to back in whole cycles of its
distinct calls, as many as end within ``--seconds`` and at least one, so
every run does the same work whatever its seed.  The process-wide settings
(compilation cache, autotune cache) are ``bench/run.py``'s.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Optional

BENCH = pathlib.Path(__file__).resolve().parent
PEAKS = BENCH / "peaks.json"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list      # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return Cell(name, int(cell["chips"]), config, mix,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def _load(path: pathlib.Path, prefix: str):
    name = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"{prefix}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: pathlib.Path, metric: str):
    """The module ``bench/metrics/<metric>.py``, with its ``read``."""
    return _load(root / "bench" / "metrics" / f"{metric}.py", "bench_metric")


def load_kind(root: pathlib.Path, kind: str):
    """The class ``Traffic`` of ``bench/traffic/kinds/<kind>.py``."""
    path = root / "bench" / "traffic" / "kinds" / f"{kind}.py"
    return _load(path, "bench_kind").Traffic


def peaks_for(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """Peak rates of ``device_kind``; an unknown device is an error."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def require_chips(chips: int) -> dict:
    """The devices JAX sees, if they are at least ``chips`` TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


class CompileCounter:
    """Programs compiled or loaded from the persistent cache (one JAX event
    for either), counted while the ``with`` block runs."""

    def __enter__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read it."""
    kind: str
    vertices: int
    edges: int
    setup_s: float
    plan_build_s: float
    window_s: float
    work: dict
    compiles_in_window: int
    peaks: dict
    trace: Optional[object] = None      # bench.trace.Reduced


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _log(msg: str) -> None:
    print(f"bench {msg}", file=sys.stderr, flush=True)


def window_done(elapsed: float, cycles: int, seconds: float) -> bool:
    """Whether the window ends after ``cycles`` whole cycles: the next one,
    at the mean pace so far, would end past ``seconds``."""
    return elapsed * (cycles + 1) / cycles > seconds


def place_graph(offsets, cols, salt, chips: int):
    """The program's ``Graph`` of the host CSR, with its weights: on the
    default device for one chip; for several, as host arrays, which the
    sharded inspector splits over the mesh (the weights computed in blocks,
    so no array of all the edges lands on a chip)."""
    import jax
    import jax.numpy as jnp
    from bench import graphs
    from repro.sparse import CSR, Graph

    n, e = offsets.size - 1, cols.size
    if chips == 1:
        offsets, cols = jnp.asarray(offsets), jnp.asarray(cols)
    weights = graphs.edge_weights(offsets, cols, salt)
    graph = Graph(CSR(offsets, cols, weights, (n, n), e))
    return jax.block_until_ready(graph)


def build_plan(graph, chips: int, options: dict):
    """The inspector's plan for ``chips`` chips, built to the end, and the
    devices it lives on: ``build_advance`` on the default device for one
    chip, ``build_sharded_advance`` over the program's own graph mesh of
    the first ``chips`` devices for several."""
    import jax
    from repro.sparse import build_advance, build_sharded_advance

    if chips == 1:
        plan = jax.block_until_ready(build_advance(graph, **options))
        return plan, [jax.devices()[0]]
    from repro.launch.mesh import make_graph_mesh
    mesh = make_graph_mesh(chips)
    plan = build_sharded_advance(graph, mesh, **options)
    jax.block_until_ready(plan.data())
    return plan, list(mesh.devices.flat)


def describe_plan(plan) -> str:
    """The plan's choices, for the log."""
    one = getattr(plan, "template", plan)    # a sharded plan's shard 0
    text = (f"pull={one.schedule.value}/{one.path.value} "
            f"push={one.push_schedule.value}/{one.push_path.value} "
            f"direction_threshold={one.direction_threshold!r} "
            f"compact_capacity={one.compact_capacity}")
    if one is not plan:
        text += (f" shards={plan.num_shards} "
                 f"shard_schedule={plan.shard_schedule}")
    return text


def memory_peaks(devices) -> list[int]:
    """``peak_bytes_in_use`` of each device (0 where the backend keeps no
    statistics)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def run_cell(root: pathlib.Path, name: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, device: dict) -> dict:
    """Set up, measure and check one cell; returns the result line."""
    with CompileCounter() as counter:
        try:
            return _run_cell(root, name, seed=seed, seconds=seconds,
                             trace=trace, t_start=t_start, device=device,
                             counter=counter)
        finally:
            gc.unfreeze()


def _run_cell(root, name, *, seed, seconds, trace, t_start, device,
              counter) -> dict:
    import jax
    import numpy as np

    from bench import graphs
    from bench.reference import Reference

    cell = find_cell(root, name)
    cfg, mix = cell.config, cell.mix
    peaks = peaks_for(device["kind"], root / "bench" / "peaks.json")
    traffic_kind = load_kind(root, mix["kind"])
    with _span("bench.generate"):
        t = time.perf_counter()
        host_offsets, host_cols, salt = graphs.generate(
            cfg, cfg["graph_seed"])
        graph = place_graph(host_offsets, host_cols, salt, cell.chips)
    n, e = graph.num_vertices, graph.num_edges
    degrees = np.diff(host_offsets)
    _log(f"generate_s={time.perf_counter() - t!r} vertices={n} "
         f"directed_edges={e} max_degree={int(degrees.max())}")
    traffic = traffic_kind(mix, seed, degrees)
    with _span("bench.plan"):
        t = time.perf_counter()
        plan, devices = build_plan(
            graph, cell.chips, {**cfg["plan"], **mix.get("plan", {})})
        plan_build_s = time.perf_counter() - t
    _log(f"plan_build_s={plan_build_s!r} {describe_plan(plan)}")
    with _span("bench.warm_up"):
        t = time.perf_counter()
        traffic.warm_up(graph, plan)
        # what tracing and compiling left behind is collected here, not in
        # the window
        gc.collect()
        gc.freeze()
    _log(f"warm_up_s={time.perf_counter() - t!r}")
    setup_s = time.perf_counter() - t_start
    _log(f"setup_s={setup_s!r} programs_in_setup={counter.count}")

    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles0 = counter.count
    call_ends = []
    if trace:
        jax.profiler.start_trace(logdir)
    try:
        with _span("bench.window"):
            t0 = time.perf_counter()
            while True:
                with _span("bench.call"):
                    traffic.call(graph, plan)
                call_ends.append(time.perf_counter() - t0)
                cycles, rest = divmod(len(call_ends), traffic.cycle)
                if not rest and window_done(call_ends[-1], cycles, seconds):
                    break
            window_s = time.perf_counter() - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = counter.count - compiles0
    call_s = np.diff(call_ends, prepend=0.0)
    _log(f"call_s={[float(x) for x in call_s]!r}")
    per_chip = memory_peaks(devices)
    device = {**device, "memory_peak_bytes": max(per_chip),
              "memory_peak_bytes_per_chip": per_chip}
    _log(f"window_s={window_s!r} programs_in_window={compiles} "
         f"memory_peak_bytes_per_chip={per_chip}")

    # the program's state goes before the reference runs
    work = traffic.collect()
    del plan, graph
    run = Run(kind=mix["kind"], vertices=n, edges=e, setup_s=setup_s,
              plan_build_s=plan_build_s, window_s=window_s, work=work,
              compiles_in_window=compiles, peaks=peaks)
    if trace:
        from bench.trace import find_xplane, reduce_trace
        run.trace = reduce_trace(find_xplane(logdir))
        shutil.rmtree(logdir)
        _log(f"trace_chips={run.trace.chips}")
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    t = time.perf_counter()
    compared, failed = traffic.check(Reference(host_offsets, host_cols))
    _log(f"check_s={time.perf_counter() - t!r} work={json.dumps(work)}")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(root, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = work["calls"] > 0 and failed == 0 and all(
        v <= limit for v, limit in compared.values())
    result = {"correct": correct, "attempted": work["calls"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": run.trace.top_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["compared"] = {k: {"value": v, "limit": limit}
                          for k, (v, limit) in compared.items()}
    for k, (v, limit) in compared.items():
        _log(f"compared {k}={v!r} limit={limit!r}")
    return result
