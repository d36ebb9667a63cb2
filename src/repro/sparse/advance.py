"""Load-balanced graph frontier operators (paper §5.3, Listing 5).

The paper's graph evaluation drives BFS/SSSP through a balanced ``advance``:
every edge leaving the frontier is one work atom, and the per-edge relax
(``atomicMin(dist[dst], dist[src] + w)``) is load-balanced exactly like a
SpMV's multiply — that is the point of the abstraction.  Atos (arXiv
2112.00132) builds the same discipline around a chunked work queue, which is
what :mod:`repro.core.dynamic` reproduces.

Two *directions* of the same advance are provided, behind one inspector:

* **Pull** (PR 3): tiles = destination vertices, atoms = in-edges of the
  transpose CSR; the relax is a per-tile ``min``-reduce over in-edges under
  a frontier mask (``frontier[src(e)]``).  Touches every edge per
  iteration — the right direction when the frontier is dense.
* **Push** (this PR): tiles = *source* vertices, atoms = out-edges of the
  forward CSR — the paper's original Listing 5 orientation.  The balanced
  executors produce frontier-compacted per-source value windows (masked to
  edges whose source tile is in the frontier) and the results are combined
  by edge *destination* through the same segmented machinery the tile
  reduces use (:func:`repro.core.execute.execute_scatter_reduce`) — the
  deterministic stand-in for ``atomicMin``'s scatter.  Only the frontier's
  out-edges carry non-identity values, which is why the cost model charges
  push by frontier density (:func:`repro.core.balance.modeled_advance_cost`)
  and why direction choice dominates sparse-frontier iterations (the §5.3 /
  Atos observation, Beamer's direction-optimizing BFS).

Because the graph's topology is static across iterations, both directions
are one-time inspector products (:func:`build_advance` returns a *plan
pair* in one call): BFS/SSSP/PageRank pay schedule construction once per
direction and re-run the balanced advance every iteration under
``lax.while_loop`` — any of the six registered schedules, either execution
path, selected by argument or by the cost-model autotuner
(``schedule="auto"`` scores the ``workload="advance"`` family for pull and
``workload="advance_push"`` for push, each under its own cache namespace).
The drivers in :mod:`repro.sparse.graph` switch directions per iteration
from the *measured* frontier out-edge count threaded through the while-loop
carry, against the plan's modeled ``direction_threshold``.

Two refinements ride the same plan pair (this PR):

* a **delta split** (:meth:`AdvancePlan.with_delta` / ``build_advance(...,
  delta=)``): per-direction light/heavy edge masks at a bucket width chosen
  from the weight distribution, which is all the delta-stepping SSSP driver
  needs — its bucket loops are ordinary advances restricted by
  ``edges="light"``/``"heavy"``; and
* **frontier compaction** (``build_advance(..., compact=)``): the push
  direction's masked windows are gather-compacted to a static capacity,
  and each advance runs the smallest rung of a ladder of halvings of it
  that holds its active edges
  (:func:`repro.core.execute.execute_scatter_reduce`), so sparse frontiers
  stream only their own out-edges — with a masked fallback past capacity,
  results never change, only streamed volume.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import (ExecutionPath, Partition, Schedule,
                        choose_execution_path, estimate_compact_capacity,
                        estimate_direction_threshold,
                        execute_scatter_reduce, execute_tile_reduce,
                        make_partition, telemetry)
from repro.core.execute import AtomFn, lane_take
from repro.core.work import WorkSpec

#: Default physical blocks for graph advance (graphs in this repo's tests
#: and benchmarks are modest; ops-layer callers can always override).
DEFAULT_NUM_BLOCKS = 32

#: Accepted ``schedule=`` spellings for the dynamic queue policies, same
#: contract as ``kernels/spmv_merge/ops.py``.
_CHUNK_POLICIES = {"chunked": "lpt", "chunked_lpt": "lpt",
                   "chunked_rr": "round_robin"}

#: Directions an advance can run in (see module docstring).
DIRECTIONS = ("pull", "push")

#: Edge subsets an advance can restrict itself to: the whole edge set, or —
#: on a plan carrying a ``delta`` split — only the light (weight <= delta)
#: or heavy (weight > delta) edges.  The delta-stepping SSSP buckets are
#: built from exactly these two restricted advances.
EDGE_SETS = ("all", "light", "heavy")


def estimate_delta(weights) -> float:
    """Bucket width for delta-stepping, from the weight distribution.

    The mean positive weight: it splits the edge set roughly in half
    (light edges drive the inner bucket loop, heavy edges are relaxed once
    per bucket) and bounds the bucket count by ``max_dist / mean_weight`` —
    the practical middle of Meyer & Sanders' Delta range (Delta -> 0 is
    Dijkstra, Delta -> inf is Bellman-Ford).  Deterministic, so plans built
    from the same graph always agree.  Edgeless graphs get 1.0 (any
    positive width: there is nothing to bucket).
    """
    w = np.asarray(weights, np.float32)
    w = w[np.isfinite(w) & (w > 0)]
    if w.size == 0:
        return 1.0
    return float(max(np.float32(w.mean()), w.min()))


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["spec", "src", "weight", "part", "push_spec", "dst",
                 "push_weight", "push_src", "push_part", "out_degrees",
                 "light_mask", "push_light_mask", "light_out_degrees"],
    meta_fields=["schedule", "path", "push_schedule", "push_path",
                 "num_vertices", "direction_threshold", "delta",
                 "compact_capacity"])
@dataclasses.dataclass(frozen=True)
class AdvancePlan:
    """One-time inspector output for a graph's advance operator — a *pair*
    of direction plans sharing one inspection pass.

    The pull fields (``spec``/``src``/``weight``/``part``/``schedule``/
    ``path``) keep their PR-3 names: tiles = destination vertices, atoms =
    in-edges of the transpose CSR.  The ``push_*`` fields hold the forward
    view: tiles = source vertices, atoms = out-edges; ``dst`` is each
    out-edge atom's destination (the scatter id), ``push_src`` its source
    tile (the frontier-mask gather, materialized once).  Built outside jit
    (partitioning is a pre-launch inspector).  A pytree: hand it to a
    jitted function as an argument, so its arrays stay device buffers —
    closed over by ``jax.jit`` they would be baked into the program as
    constants — while its statics ride the treedef.

    ``direction_threshold`` is the modeled frontier (out-edge) density at
    which pull becomes cheaper than push
    (:func:`repro.core.balance.estimate_direction_threshold`); the
    direction-optimizing drivers compare the measured density against it
    every iteration.  ``out_degrees`` rides along so that measurement is
    one masked sum in the carry.
    """

    # -- pull direction (PR-3 field names kept) -----------------------------
    spec: WorkSpec            # pull view: tiles = destinations
    src: jax.Array            # [E] int32 source vertex of each in-edge atom
    weight: jax.Array         # [E] f32 weight of each in-edge atom
    part: Partition
    schedule: Schedule
    path: ExecutionPath
    # -- push direction -----------------------------------------------------
    push_spec: WorkSpec       # push view: tiles = sources
    dst: jax.Array            # [E] int32 destination of each out-edge atom
    push_weight: jax.Array    # [E] f32 weight of each out-edge atom
    push_src: jax.Array       # [E] int32 source tile of each out-edge atom
    push_part: Partition
    push_schedule: Schedule
    push_path: ExecutionPath
    # -- shared -------------------------------------------------------------
    num_vertices: int
    out_degrees: jax.Array    # [V] int32 (measured-density term)
    direction_threshold: float
    # -- bucketed (delta-stepping) view: set by with_delta/build_advance ----
    delta: Optional[float] = None
    light_mask: Optional[jax.Array] = None       # [E] bool, pull edge order
    push_light_mask: Optional[jax.Array] = None  # [E] bool, push edge order
    light_out_degrees: Optional[jax.Array] = None  # [V] int32
    # -- frontier compaction: static capacity of the gather-compacted push
    #    windows (None = masked full windows, the PR-4 behaviour) ----------
    compact_capacity: Optional[int] = None

    @property
    def num_edges(self) -> int:
        return self.push_spec.num_atoms

    def with_compact_capacity(self,
                              capacity: Optional[int]) -> "AdvancePlan":
        """Same plan pair, different static push-compaction capacity.

        Pure bookkeeping (no re-inspection): the capacity only sizes the
        gather-compacted window mode of
        :func:`repro.core.execute.execute_scatter_reduce`, whose runtime
        ``lax.cond`` falls back to masked full windows whenever the
        measured active count exceeds it — so any capacity is correct.
        The delta-stepping driver uses this to hand its light bucket
        phases a capacity clamped to the light edge-set size (the largest
        measured light density any bucket can reach), keeping sparse
        bucket frontiers on the compact path without rebuilding the
        partitions.  ``None`` disables compaction on the returned plan.
        """
        if capacity is not None:
            capacity = int(capacity)
            if capacity < 1:
                raise ValueError(f"compact capacity must be >= 1 or None, "
                                 f"got {capacity}")
        return dataclasses.replace(self, compact_capacity=capacity)

    def with_delta(self, delta: Optional[float] = None) -> "AdvancePlan":
        """Attach a light/heavy edge split (bucket width ``delta``).

        Materializes the per-direction light masks (pull and push edge
        orders differ, so both are stored) and the light out-degree array
        the drivers measure light-frontier density with.  ``None`` picks
        :func:`estimate_delta` from this plan's weight distribution.  Pure
        bookkeeping over arrays the plan already owns — no re-inspection.
        """
        if delta is None:
            delta = estimate_delta(self.push_weight)
        delta = float(delta)
        if not delta > 0.0:
            raise ValueError(f"delta must be positive, got {delta}")
        light_mask, push_light, light_out = _delta_edge_split(
            delta, self.weight, self.push_weight, self.push_src,
            self.num_vertices)
        return dataclasses.replace(
            self, delta=delta, light_mask=light_mask,
            push_light_mask=push_light, light_out_degrees=light_out)

    def edge_set_mask(self, edges: str, direction: str) -> Optional[jax.Array]:
        """The requested edge subset as a per-atom mask in ``direction``'s
        own edge order (``None`` for the full set)."""
        if edges not in EDGE_SETS:
            raise ValueError(f"unknown edge set: {edges!r} "
                             f"(expected one of {EDGE_SETS})")
        if edges == "all":
            return None
        if self.delta is None:
            raise ValueError(
                f"edges={edges!r} needs a delta split on the plan; build "
                f"with delta= or call plan.with_delta()")
        light = (self.push_light_mask if direction == "push"
                 else self.light_mask)
        return light if edges == "light" else jnp.logical_not(light)

    def edge_fraction(self, active_edge_count: jax.Array) -> jax.Array:
        """Fraction of the edge set a given active out-edge count covers —
        the one definition of measured density the drivers and tests share
        (compared against ``direction_threshold``)."""
        return active_edge_count.astype(jnp.float32) / jnp.float32(
            max(self.num_edges, 1))

    def frontier_edge_fraction(self, frontier: jax.Array) -> jax.Array:
        """Measured frontier density: fraction of edges leaving ``frontier``.

        One masked sum over the static out-degree array — cheap enough to
        thread through a ``while_loop`` carry every iteration, which is
        what makes the direction switch *measured* rather than guessed.
        """
        return self.edge_fraction(
            jnp.sum(jnp.where(frontier, self.out_degrees, 0)))


def _delta_edge_split(delta: float, pull_weight: jax.Array,
                      push_weight: jax.Array, push_src: jax.Array,
                      num_vertices: int):
    """Light/heavy edge split at bucket width ``delta``, both directions in
    one pass.

    The threshold compare runs once per distinct weight array and the light
    out-degree segment sum runs once total (over the push view, which owns
    the out-edges) — previously each direction recomputed its own degree
    term.  Shared by :meth:`AdvancePlan.with_delta` and the per-shard local
    views in :mod:`repro.sparse.shard`.  Returns ``(light_mask,
    push_light_mask, light_out_degrees)``.
    """
    thr = jnp.float32(delta)
    push_light = push_weight <= thr
    light_out = (jax.ops.segment_sum(push_light.astype(jnp.int32), push_src,
                                     num_segments=num_vertices)
                 if num_vertices else jnp.zeros((0,), jnp.int32))
    return pull_weight <= thr, push_light, light_out


def _resolve_direction_plan(spec: WorkSpec, schedule, path, num_blocks: int,
                            workload: str, measure=None):
    """(schedule, policy, path, Partition) for one direction's work view."""
    policy = _CHUNK_POLICIES.get(str(schedule))
    sched = Schedule.CHUNKED if policy else Schedule(schedule)
    req_path = ExecutionPath(path)
    if sched == Schedule.AUTO:
        from repro.core.autotune import select_plan
        with telemetry.span("inspect.autotune"):
            plan = select_plan(spec, num_blocks, workload=workload,
                               measure=measure)
        sched = plan.schedule
        policy = "lpt" if sched == Schedule.CHUNKED else None
        if req_path == ExecutionPath.AUTO:
            req_path = plan.path
    part = make_partition(spec, sched, num_blocks,
                          chunk_policy=policy or "lpt")
    return sched, choose_execution_path(part, req_path), part


def _direction_measure(spec: WorkSpec, gather: jax.Array, num_blocks: int,
                       direction: str, weight: jax.Array,
                       num_vertices: int, dst: Optional[jax.Array]):
    """Default measured-mode timing closure for one direction's candidates.

    Times each candidate (schedule, path) plan on this graph's *actual*
    relax workload (min-combine of ``potentials[src] + w`` under a
    representative ~30% frontier — between the sparse and dense regimes
    the direction threshold separates) via
    :func:`repro.core.measure.time_fn`.  Only consulted when
    ``REPRO_AUTOTUNE_MEASURE`` is on; the measured medians land in the v2
    autotune cache under the direction's own workload namespace.
    """
    from repro.core.measure import time_fn
    rng = np.random.default_rng(0)
    frontier = jnp.asarray(rng.random(max(num_vertices, 1)) < 0.3)
    potentials = jnp.zeros((max(num_vertices, 1),), jnp.float32)
    w = weight.astype(jnp.float32)

    def run(plan) -> float:
        part = make_partition(spec, plan.schedule, num_blocks,
                              chunk_policy="lpt")
        mask = frontier[gather]
        atom_fn = lambda e, p: p[gather[e]] + w[e]
        if direction == "push":
            @jax.jit
            def f(p):
                return execute_scatter_reduce(
                    spec, part, lambda e: atom_fn(e, p), dst, num_vertices,
                    jnp.float32, path=plan.path, combiner="min",
                    atom_mask=mask)
        else:
            @jax.jit
            def f(p):
                return execute_tile_reduce(
                    spec, part, lambda e: atom_fn(e, p), jnp.float32,
                    path=plan.path, combiner="min", atom_mask=mask)
        return time_fn(f, potentials, warmup=1, iters=3)
    return run


#: Push-direction sibling of each frontier-masked workload family; other
#: families (e.g. "reduce" for PageRank's unmasked full sweeps) apply to
#: both directions as-is.
_PUSH_WORKLOADS = {"advance": "advance_push",
                   "advance_delta": "advance_delta_push",
                   "advance_serve": "advance_serve_push",
                   "wavefront": "wavefront_push"}


@telemetry.span("inspect")
def build_advance(graph, *, schedule: Schedule | str = "auto",
                  num_blocks: Optional[int] = None,
                  path: ExecutionPath | str = ExecutionPath.AUTO,
                  workload: str = "advance",
                  direction_threshold: Optional[float] = None,
                  delta: Optional[float | str] = None,
                  compact: Optional[bool | int | float] = None,
                  measure=None) -> AdvancePlan:
    """Inspect a :class:`~repro.sparse.graph.Graph` into an AdvancePlan pair.

    One inspector call builds *both* directions: the pull partition over the
    transpose CSR and the push partition over the forward CSR.  ``schedule``
    accepts every registered schedule, the dynamic queue spellings
    (``"chunked"``/``"chunked_lpt"``/``"chunked_rr"``), or ``"auto"`` —
    which asks :func:`repro.core.autotune.select_plan` for a (schedule,
    path) plan per direction: the ``workload`` cost family (default
    ``"advance"``; ``"reduce"`` for unmasked full sweeps like PageRank) for
    pull, and the ``"advance_push"`` family — its own cache namespace —
    for push, so schedule and direction are selected jointly from the same
    cost model.  ``path`` resolves against each built partition exactly
    like the SpMV ops wrapper.

    ``direction_threshold`` overrides the modeled push->pull switch density
    (:func:`repro.core.balance.estimate_direction_threshold`); pass ``0.0``
    to force pull-only or ``1.0`` push-only behaviour in the
    direction-optimizing drivers without rebuilding anything.

    ``delta`` attaches the light/heavy bucket split for delta-stepping
    (``"auto"`` estimates the width from the weight distribution — see
    :func:`estimate_delta`; a float pins it).  ``compact`` enables the
    gather-compacted push window mode (ROADMAP's frontier compaction):
    ``True`` sizes the static capacity from the direction threshold
    (:func:`repro.core.balance.estimate_compact_capacity`), a float in
    (0, 1] is a fraction of the edge set, an int >= 1 an exact slot count.
    Overflowing frontiers fall back to masked full windows inside the
    executor, so compaction never changes results — only streamed volume.

    ``measure`` is the measured-cost feedback knob (docs/autotune.md): with
    ``REPRO_AUTOTUNE_MEASURE=1`` and ``schedule="auto"``, each direction's
    candidate plans are *timed on this graph's own relax workload* (see
    :func:`_direction_measure`) and the autotuner re-ranks by measurement.
    ``None`` builds the default per-direction timing closures when the env
    gate is on; ``False`` keeps selection model-only regardless; a callable
    ``(direction, plan) -> median_us`` supplies custom timings.
    """
    num_blocks = DEFAULT_NUM_BLOCKS if num_blocks is None else num_blocks
    pull = graph.csr.transpose()          # CSR of A^T: rows = destinations
    spec = pull.workspec()
    push_spec = graph.csr.workspec()      # forward CSR: rows = sources
    push_ids = push_spec.atom_tile_ids()  # once: measure closure + plan
    pull_measure = push_measure = None
    if measure is not False and str(schedule) not in _CHUNK_POLICIES \
            and Schedule(schedule) == Schedule.AUTO:
        from repro.core.autotune import measurement_enabled
        if callable(measure):
            pull_measure = lambda p: measure("pull", p)
            push_measure = lambda p: measure("push", p)
        elif measurement_enabled():
            pull_measure = _direction_measure(
                spec, pull.col_indices, num_blocks, "pull",
                pull.values, graph.num_vertices, None)
            push_measure = _direction_measure(
                push_spec, push_ids, num_blocks, "push",
                graph.csr.values, graph.num_vertices,
                graph.csr.col_indices)
    return build_advance_views(
        pull_spec=spec, pull_src=pull.col_indices, pull_weight=pull.values,
        push_spec=push_spec, push_dst=graph.csr.col_indices,
        push_weight=graph.csr.values, push_src=push_ids,
        num_vertices=graph.num_vertices,
        schedule=schedule, num_blocks=num_blocks, path=path,
        workload=workload, direction_threshold=direction_threshold,
        delta=delta, compact=compact,
        pull_measure=pull_measure, push_measure=push_measure)


@telemetry.span("inspect")
def build_advance_views(*, pull_spec: WorkSpec, pull_src: jax.Array,
                        pull_weight: jax.Array, push_spec: WorkSpec,
                        push_dst: jax.Array, push_weight: jax.Array,
                        push_src: Optional[jax.Array] = None,
                        num_vertices: int,
                        schedule: Schedule | str = "auto",
                        num_blocks: Optional[int] = None,
                        path: ExecutionPath | str = ExecutionPath.AUTO,
                        workload: str = "advance",
                        direction_threshold: Optional[float] = None,
                        delta: Optional[float | str] = None,
                        compact: Optional[bool | int | float] = None,
                        pull_measure=None, push_measure=None,
                        out_degrees: Optional[jax.Array] = None) -> AdvancePlan:
    """The view-level inspector core behind :func:`build_advance`.

    Takes the two work views directly (pull: tiles = destinations over
    ``pull_spec`` with per-atom ``pull_src``/``pull_weight``; push: tiles =
    sources over ``push_spec`` with per-atom ``push_dst``/``push_weight``)
    instead of a :class:`~repro.sparse.graph.Graph`, so the same
    partitioning/threshold/compaction logic serves both the whole-graph
    build and the per-shard local views of
    :func:`repro.sparse.shard.build_sharded_advance` — where the views are
    *slices* of the global CSRs rebased to a shard's vertex range and the
    caller overrides ``push_src`` (global source ids, not local tile ids)
    and ``out_degrees`` (owned vertices only, pad tiles excluded).

    ``pull_measure``/``push_measure`` are pre-built per-direction timing
    closures (or ``None``); everything else matches :func:`build_advance`.
    """
    num_blocks = DEFAULT_NUM_BLOCKS if num_blocks is None else num_blocks
    with telemetry.span("inspect.pull"):
        sched, resolved, part = _resolve_direction_plan(
            pull_spec, schedule, path, num_blocks, workload,
            measure=pull_measure)
    push_workload = _PUSH_WORKLOADS.get(workload, workload)
    with telemetry.span("inspect.push"):
        push_sched, push_resolved, push_part = _resolve_direction_plan(
            push_spec, schedule, path, num_blocks, push_workload,
            measure=push_measure)
    if direction_threshold is None:
        direction_threshold = estimate_direction_threshold(
            pull_spec, push_spec, num_blocks,
            pull_schedule=sched, push_schedule=push_sched,
            pull_path=str(resolved), push_path=str(push_resolved),
            pull_part=part, push_part=push_part)
    num_edges = push_spec.num_atoms
    if compact is None or compact is False:
        capacity = None
    elif compact is True:
        capacity = estimate_compact_capacity(num_edges,
                                             float(direction_threshold))
    elif isinstance(compact, float):
        if not 0.0 < compact <= 1.0:
            raise ValueError(f"compact fraction must be in (0, 1], "
                             f"got {compact}")
        capacity = max(int(np.ceil(num_edges * compact)), 1)
    else:
        if int(compact) < 1:
            raise ValueError(f"compact capacity must be >= 1 (or None/"
                             f"False to disable), got {compact}")
        capacity = int(compact)
    if push_src is None:
        push_src = push_spec.atom_tile_ids()
    if out_degrees is None:
        out_degrees = push_spec.atoms_per_tile()
    plan = AdvancePlan(
        spec=pull_spec, src=pull_src,
        weight=pull_weight.astype(jnp.float32), part=part,
        schedule=sched, path=resolved,
        push_spec=push_spec, dst=push_dst,
        push_weight=push_weight.astype(jnp.float32),
        push_src=push_src, push_part=push_part,
        push_schedule=push_sched, push_path=push_resolved,
        num_vertices=num_vertices,
        out_degrees=out_degrees.astype(jnp.int32),
        direction_threshold=float(direction_threshold),
        compact_capacity=capacity)
    if delta is not None:
        plan = plan.with_delta(None if delta == "auto" else delta)
    return plan


@jax.named_scope("mask")
def _combined_mask(vertex_mask: Optional[jax.Array], gather: jax.Array,
                   edge_mask: Optional[jax.Array]) -> Optional[jax.Array]:
    """frontier-gather AND edge-subset mask (either may be absent)."""
    atom_mask = (None if vertex_mask is None
                 else lane_take(vertex_mask, gather))
    if edge_mask is None:
        return atom_mask
    return edge_mask if atom_mask is None else jnp.logical_and(atom_mask,
                                                               edge_mask)


def advance(plan: AdvancePlan, frontier: Optional[jax.Array],
            atom_fn: AtomFn, *,
            combiner: str = "sum",
            edge_mask: Optional[jax.Array] = None) -> jax.Array:
    """The pull-direction balanced advance: per-destination ``combiner``-
    reduce over in-edge atoms, masked to edges whose *source* is in the
    frontier.

    ``frontier`` is a bool ``[V]`` vertex mask (``None`` = all active);
    ``atom_fn`` maps **in-edge atom ids** (pull order) to f32 candidate
    values (Listing 5's loop body), or is those ``[E]`` values.
    ``edge_mask`` (bool ``[E]``, pull edge order) further restricts the
    atom set — the delta-stepping light/heavy split
    (:meth:`AdvancePlan.edge_set_mask`).  Returns ``[V]`` f32;
    destinations with no active in-edge carry the combiner's identity.
    Routed through :func:`repro.core.execute.execute_tile_reduce`, so every
    schedule and both execution paths produce identical bits.
    """
    atom_mask = _combined_mask(frontier, plan.src, edge_mask)
    return execute_tile_reduce(plan.spec, plan.part, atom_fn, jnp.float32,
                               path=plan.path, combiner=combiner,
                               atom_mask=atom_mask)


def advance_push(plan: AdvancePlan, frontier: Optional[jax.Array],
                 atom_fn: AtomFn, *,
                 combiner: str = "sum",
                 edge_mask: Optional[jax.Array] = None) -> jax.Array:
    """The push-direction balanced advance (Listing 5's own orientation).

    ``atom_fn`` maps **out-edge atom ids** (push/forward order) to f32
    candidate values, or is those ``[E]`` values; ``edge_mask`` (bool
    ``[E]``, push edge order) is the delta-stepping light/heavy
    restriction.  The balanced executors walk
    the push partition (tiles = source vertices) producing
    frontier-compacted per-source value windows;
    :func:`repro.core.execute.scatter_value_windows` then combines them by
    each edge's destination — the same segmented machinery as the tile
    reduces, so every schedule and both execution paths produce identical
    bits, and (for the exact min/max combiners or exactly summable values)
    the same bits as the pull advance over the same edge multiset.

    On a plan built with ``compact=...`` the masked atoms are additionally
    *gather-compacted* before streaming (into the smallest rung of the
    ladder below ``compact_capacity`` slots that holds them, with an
    in-executor masked fallback past capacity) — sparse frontiers stream
    only their own out-edges instead of masking full windows, without
    changing a single result bit.
    """
    atom_mask = _combined_mask(frontier, plan.push_src, edge_mask)
    return execute_scatter_reduce(plan.push_spec, plan.push_part, atom_fn,
                                  plan.dst, plan.num_vertices, jnp.float32,
                                  path=plan.push_path, combiner=combiner,
                                  atom_mask=atom_mask,
                                  compact_capacity=plan.compact_capacity)


def _check_direction(direction: str) -> str:
    if direction not in DIRECTIONS:
        raise ValueError(f"unknown direction: {direction!r} "
                         f"(expected one of {DIRECTIONS})")
    return direction


def advance_relax_min(plan: AdvancePlan, potentials: jax.Array,
                      frontier: Optional[jax.Array], *,
                      direction: str = "pull",
                      edges: str = "all") -> jax.Array:
    """SSSP relax (Listing 5): ``cand[v] = min over edges (u, v) of
    potentials[u] + w(u, v)``.

    ``direction="pull"`` is the segmented form of ``atomicMin``;
    ``"push"`` computes the identical candidate per edge (same two f32
    operands, same rounding) on the forward view and scatters by
    destination — min is exact, so both directions return identical bits.
    ``edges="light"``/``"heavy"`` restricts the relax to one side of the
    plan's delta split (the delta-stepping bucket loops); the restriction
    is a mask over the same candidate multiset, so direction equivalence
    holds per subset too.
    """
    edge_mask = plan.edge_set_mask(edges, _check_direction(direction))
    if direction == "push":
        src, w = plan.push_src, plan.push_weight
        return advance_push(plan, frontier, lane_take(potentials, src) + w,
                            combiner="min", edge_mask=edge_mask)
    src, w = plan.src, plan.weight
    return advance(plan, frontier, lane_take(potentials, src) + w,
                   combiner="min", edge_mask=edge_mask)


def advance_frontier(plan: AdvancePlan, frontier: jax.Array, *,
                     direction: str = "pull") -> jax.Array:
    """Scatter-or: which destinations have at least one active edge.

    The max-combiner over unit values; identity ``-inf`` at untouched
    destinations, so the threshold test recovers the bool mask in either
    direction.
    """
    unit = lambda e: jnp.ones(e.shape, jnp.float32)
    if _check_direction(direction) == "push":
        reached = advance_push(plan, frontier, unit, combiner="max")
    else:
        reached = advance(plan, frontier, unit, combiner="max")
    return reached > 0.0


def advance_src_argmin(plan: AdvancePlan, frontier: jax.Array, *,
                       direction: str = "pull") -> jax.Array:
    """Smallest active in-neighbour per destination (BFS parent pointers).

    Vertex ids reduce exactly as f32 up to 2**24 vertices (enforced loudly:
    beyond that the min-combiner could return a rounded, wrong parent);
    destinations with no active in-edge come back as ``-1``.  Min over the
    same id multiset — directions agree bitwise.
    """
    if plan.num_vertices >= (1 << 24):
        raise ValueError(
            f"advance_src_argmin: vertex ids are reduced as f32, exact only "
            f"below 2**24 vertices (got {plan.num_vertices})")
    if _check_direction(direction) == "push":
        src = plan.push_src
        cand = advance_push(plan, frontier, src.astype(jnp.float32),
                            combiner="min")
    else:
        src = plan.src
        cand = advance(plan, frontier, src.astype(jnp.float32),
                       combiner="min")
    return jnp.where(jnp.isfinite(cand), cand, -1.0).astype(jnp.int32)


def frontier_filter(plan: AdvancePlan, frontier: jax.Array,
                    keep: Optional[jax.Array] = None, *,
                    direction: str = "pull") -> jax.Array:
    """The paper's ``filter``: next frontier = unique destinations of active
    edges, minus those failing ``keep``.

    The expensive half of a GPU filter — deduplicating the scattered
    destination list — *is* the max-combiner reduce above (each destination
    collapses its active edges to one bit, in either direction); under TPU
    static shapes the compaction half degenerates to a mask-and, which is
    exactly what downstream advances consume.
    """
    nxt = advance_frontier(plan, frontier, direction=direction)
    if keep is not None:
        nxt = jnp.logical_and(nxt, keep)
    return nxt
