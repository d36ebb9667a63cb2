"""Data-centric graph algorithms on the load-balancing abstraction (§5.3).

BFS / SSSP / PageRank are frontier-based *advance* operations: atoms = edges
of the graph, tiles = vertices — the same WorkSpec vocabulary as SpMV.  The
paper's Listing 5 loops over assigned edges, finds each edge's tile via
``get_tile(edge)``, and relaxes with ``atomicMin``.

All three drivers here are thin iteration loops around
:mod:`repro.sparse.advance`: the graph topology is inspected **once** into
an :class:`~repro.sparse.advance.AdvancePlan` (a pull/push plan *pair*),
then every iteration runs the balanced advance through
``repro.core.execute`` — any registered schedule (static, chunked queue,
adaptive, or cost-model ``"auto"``), either execution path (pure blocked
executor or the native chunk-walking Pallas kernel), selected by argument.
Iterations run under ``lax.while_loop`` — the host-side analogue of
persistent-kernel mode (paper §5.1 ``infinite_range``), since Pallas has no
device-wide sync.

**Direction optimization** (Beamer's push/pull switch, the §5.3 traversal
regime): with ``direction="auto"`` (the default) BFS and SSSP measure the
frontier's out-edge fraction — a masked sum threaded through the while-loop
carry — and run the *push* advance (only frontier out-edges do work) while
the frontier is sparse, switching to *pull* (stream all in-edges, no
scatter) once the measured density crosses the plan's modeled
``direction_threshold``.  Both directions produce identical bits for the
exact min/max combiners, so switching never changes results — only cost.

**Bucketed traversal** (this PR): :func:`delta_stepping` (also
``sssp(algorithm="delta")``) runs Meyer & Sanders' delta-stepping as
nested while-loops of light/heavy-restricted advances over the same plan
pair — bit-identical to Bellman-Ford for every bucket width, because both
run f32 relaxation to the same fixed point.  Concrete out-of-range sources
raise at build time in every driver (under jit they would silently clamp
into wrong-but-plausible results).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ExecutionPath, Schedule, telemetry
from repro.core.execute import lane_take
from repro.sparse.advance import (AdvancePlan, advance, advance_frontier,
                                  advance_push, advance_relax_min,
                                  advance_src_argmin, build_advance,
                                  estimate_delta)
from repro.sparse.formats import CSR

INF = jnp.float32(jnp.inf)

#: Accepted ``direction=`` spellings for the traversal drivers.
_DRIVER_DIRECTIONS = ("auto", "pull", "push")

#: Accepted ``algorithm=`` spellings for :func:`sssp`.
_SSSP_ALGORITHMS = ("bellman_ford", "delta")

#: Bucket index standing in for +inf distances (far above any reachable
#: bucket: distances are clamped into int32 range before the floor).
_FAR_BUCKET = jnp.int32(2 ** 30)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """Directed graph as CSR adjacency; ``weights`` parallel to edges."""

    csr: CSR

    def tree_flatten(self):
        return ((self.csr,), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        (csr,) = children
        return cls(csr)

    @property
    def num_vertices(self) -> int:
        return self.csr.shape[0]

    @property
    def num_edges(self) -> int:
        return self.csr.nnz

    def edge_sources(self) -> jax.Array:
        """tile-of-atom: the paper's ``get_tile(edge)`` for every edge."""
        return self.csr.workspec().atom_tile_ids()

    def out_degrees(self) -> jax.Array:
        return self.csr.workspec().atoms_per_tile()

    def advance_plan(self, *, schedule: Schedule | str = "auto",
                     num_blocks: Optional[int] = None,
                     path: ExecutionPath | str = ExecutionPath.AUTO,
                     workload: str = "advance",
                     direction_threshold: Optional[float] = None) -> AdvancePlan:
        """One-time inspector: see :func:`repro.sparse.advance.build_advance`."""
        return build_advance(self, schedule=schedule, num_blocks=num_blocks,
                             path=path, workload=workload,
                             direction_threshold=direction_threshold)


def _resolve_plan(graph: Graph, plan: Optional[AdvancePlan],
                  schedule, num_blocks, path,
                  workload: str = "advance", delta=None,
                  compact=None) -> AdvancePlan:
    if plan is not None:
        return plan
    return build_advance(graph, schedule=schedule, num_blocks=num_blocks,
                         path=path, workload=workload, delta=delta,
                         compact=compact)


def _wants_sharded(plan, mesh) -> bool:
    """Route to the device-sharded drivers?  Either an explicit ``mesh=``
    request or a prebuilt :class:`~repro.sparse.shard.ShardedAdvancePlan`
    (the one plan type that is not an :class:`AdvancePlan`)."""
    return mesh is not None or (plan is not None
                                and not isinstance(plan, AdvancePlan))


def _resolve_sharded_plan(graph: Graph, plan, mesh, schedule, num_blocks,
                          path, workload: str = "advance",
                          delta=None, compact=None, shard_schedule=None):
    """The sharded sibling of :func:`_resolve_plan` (lazy import: the shard
    module pulls in mesh/collective machinery single-device users never
    touch)."""
    from repro.sparse import shard as _shard
    if plan is not None:
        if not isinstance(plan, _shard.ShardedAdvancePlan):
            raise TypeError(
                f"mesh= traversal needs a ShardedAdvancePlan (from "
                f"build_sharded_advance), got {type(plan).__name__}")
        return _shard, plan
    return _shard, _shard.build_sharded_advance(
        graph, mesh, schedule=schedule, num_blocks=num_blocks, path=path,
        workload=workload, shard_schedule=shard_schedule, delta=delta,
        compact=compact)


def _check_driver_direction(direction: str) -> str:
    if direction not in _DRIVER_DIRECTIONS:
        raise ValueError(f"unknown direction: {direction!r} "
                         f"(expected one of {_DRIVER_DIRECTIONS})")
    return direction


def _validate_sources(sources, num_vertices: int, *,
                      what: str = "source") -> None:
    """Reject out-of-range traversal sources at build time.

    Under jit, ``dist0.at[source].set(0.0)`` and ``ids == source`` silently
    clamp/drop out-of-range indices and negative sources wrap Python-style,
    so a bad source returns wrong-but-plausible labels instead of failing.
    The drivers run this host-side check on every *concrete* source (the
    common case — sources are inspector-time inputs, like the plan);
    traced sources pass through unchecked, as any shape-polymorphic jit
    argument must.
    """
    if isinstance(sources, jax.core.Tracer):
        return
    arr = np.asarray(sources)
    if arr.size == 0:
        return
    if not np.issubdtype(arr.dtype, np.integer):
        arr = arr.astype(np.int64)
    lo, hi = int(arr.min()), int(arr.max())
    if lo < 0 or hi >= num_vertices:
        bad = arr[(arr < 0) | (arr >= num_vertices)]
        raise ValueError(
            f"{what} out of range for graph with {num_vertices} "
            f"vertices: {bad.reshape(-1)[:8].tolist()} (valid range "
            f"[0, {num_vertices - 1}])" if num_vertices else
            f"{what} {bad.reshape(-1)[:8].tolist()} on an empty graph "
            f"(no valid sources)")


@jax.named_scope("frontier")
def _active_edge_count(plan: AdvancePlan, frontier: jax.Array) -> jax.Array:
    """Out-edges leaving the frontier — the measured-density carry term."""
    return jnp.sum(jnp.where(frontier, plan.out_degrees, 0)).astype(jnp.int32)


def _directed(plan: AdvancePlan, direction: str, active_edges: jax.Array,
              push_fn, pull_fn):
    """Run one advance in the requested / measured-density direction.

    ``direction`` is static; for ``"auto"`` the switch is a traced
    ``lax.cond`` on the carried active-out-edge count against the plan's
    modeled threshold, so only the chosen branch executes at runtime.
    Returns ``(result, used_push)``.
    """
    push = jax.named_scope("push")(push_fn)
    pull = jax.named_scope("pull")(pull_fn)
    if direction == "push":
        return push(), jnp.bool_(True)
    if direction == "pull":
        return pull(), jnp.bool_(False)
    density = plan.edge_fraction(active_edges)
    use_push = density < jnp.float32(plan.direction_threshold)
    return (jax.lax.cond(use_push, lambda _: push(), lambda _: pull(),
                         operand=None), use_push)


def _relax_directed(aplan: AdvancePlan, direction: str, dist: jax.Array,
                    frontier: jax.Array, active_edges: jax.Array,
                    edges: str = "all"):
    """One direction-resolved min-relax; returns (new_dist, used_push)."""
    cand, used_push = _directed(
        aplan, direction, active_edges,
        lambda: advance_relax_min(aplan, dist, frontier, direction="push",
                                  edges=edges),
        lambda: advance_relax_min(aplan, dist, frontier, direction="pull",
                                  edges=edges))
    return jnp.minimum(dist, cand), used_push


def sssp(graph: Graph, source: int, *, max_iters: Optional[int] = None,
         schedule: Schedule | str = "auto",
         num_blocks: Optional[int] = None,
         path: ExecutionPath | str = ExecutionPath.AUTO,
         plan: Optional[AdvancePlan] = None,
         mesh=None,
         shard_schedule: Optional[str] = None,
         direction: str = "auto",
         algorithm: str = "bellman_ford",
         delta: Optional[float] = None,
         return_direction_counts: bool = False):
    """Single-source shortest path; returns distances [V] (inf = unreached).

    ``algorithm="bellman_ford"`` (default) is the frontier-driven
    Bellman-Ford of PR 3/4: each iteration relaxes every edge whose source
    improved last round (Listing 5's advance, min-combiner), then the
    frontier filter keeps only the vertices whose distance just dropped.
    ``algorithm="delta"`` routes to :func:`delta_stepping` (bucketed
    traversal over the same plan pair; ``delta`` pins the bucket width).
    Both algorithms run every edge relaxation to quiescence with the exact
    min combiner, so their distances are **bit-identical** for every delta,
    schedule, path, and direction policy.

    ``direction`` picks the advance orientation per iteration (``"auto"``:
    measured density vs. the plan threshold); min is exact, so every
    direction policy returns identical bits.
    ``return_direction_counts=True`` appends an int32 ``[2]``
    ``(push_iterations, pull_iterations)`` array, exactly like
    :func:`bfs` — the evidence the SSSP direction switch actually moves.

    ``mesh`` (shard count, 1-axis :class:`~jax.sharding.Mesh`, or
    ``"auto"``) runs the traversal device-sharded — see
    :mod:`repro.sparse.shard`; distances stay bit-identical for every
    boundary schedule (``shard_schedule`` from
    :data:`repro.sparse.shard.SHARD_SCHEDULES`, default equal-width).
    """
    _check_driver_direction(direction)
    if algorithm not in _SSSP_ALGORITHMS:
        raise ValueError(f"unknown algorithm: {algorithm!r} "
                         f"(expected one of {_SSSP_ALGORITHMS})")
    if algorithm == "delta":
        return delta_stepping(graph, source, delta=delta,
                              max_iters=max_iters, schedule=schedule,
                              num_blocks=num_blocks, path=path, plan=plan,
                              mesh=mesh, shard_schedule=shard_schedule,
                              direction=direction,
                              return_direction_counts=return_direction_counts)
    if _wants_sharded(plan, mesh):
        _shard, splan = _resolve_sharded_plan(graph, plan, mesh, schedule,
                                              num_blocks, path,
                                              shard_schedule=shard_schedule)
        return _shard.sharded_sssp(
            splan, source, max_iters=max_iters, direction=direction,
            return_direction_counts=return_direction_counts)
    with telemetry.span("sssp"):
        V = graph.num_vertices
        _validate_sources(source, V)
        max_iters = V if max_iters is None else max_iters
        with telemetry.span("sssp.plan"):
            aplan = _resolve_plan(graph, plan, schedule, num_blocks, path)
        with telemetry.span("sssp.dispatch"):
            dist, counts = _sssp_loop(aplan, jnp.asarray(source, jnp.int32),
                                      max_iters=int(max_iters),
                                      direction=direction)
    if return_direction_counts:
        return dist, counts
    return dist


# The traversal loops below run under jax.jit with the plan as an argument:
# one compiled program per plan shape serves every source, and the plan's
# arrays stay device buffers instead of being traced into a new program on
# every call.
@functools.partial(jax.jit, static_argnames=("max_iters", "direction"))
def _sssp_loop(aplan: AdvancePlan, source: jax.Array, *, max_iters: int,
               direction: str):
    """Frontier Bellman-Ford; returns ``(dist, (push, pull) counts)``."""
    V = aplan.num_vertices
    dist0 = jnp.full((V,), INF).at[source].set(0.0)
    frontier0 = jnp.zeros((V,), bool).at[source].set(True)

    def cond(state):
        i, _, frontier, _, _ = state
        return jnp.logical_and(i < max_iters, frontier.any())

    @jax.named_scope("sssp.iter")
    def body(state):
        i, dist, frontier, active_edges, pushes = state
        new_dist, used_push = _relax_directed(aplan, direction, dist,
                                              frontier, active_edges)
        new_frontier = new_dist < dist
        return (i + 1, new_dist, new_frontier,
                _active_edge_count(aplan, new_frontier),
                pushes + used_push.astype(jnp.int32))

    iters, dist, _, _, pushes = jax.lax.while_loop(
        cond, body, (0, dist0, frontier0,
                     _active_edge_count(aplan, frontier0), jnp.int32(0)))
    return dist, jnp.stack([pushes, jnp.int32(iters) - pushes])


def _bucket_of(dist: jax.Array, delta: float) -> jax.Array:
    """floor(dist / delta) as int32; +inf (unreached) maps far away."""
    b = jnp.floor(dist / jnp.float32(delta))
    b = jnp.minimum(b, jnp.float32(_FAR_BUCKET - 1))
    return jnp.where(jnp.isfinite(dist), b.astype(jnp.int32), _FAR_BUCKET)


def delta_stepping(graph: Graph, source: int, *,
                   delta: Optional[float] = None,
                   max_iters: Optional[int] = None,
                   schedule: Schedule | str = "auto",
                   num_blocks: Optional[int] = None,
                   path: ExecutionPath | str = ExecutionPath.AUTO,
                   plan: Optional[AdvancePlan] = None,
                   mesh=None,
                   shard_schedule: Optional[str] = None,
                   direction: str = "auto",
                   compact: Optional[bool | int | float] = True,
                   return_direction_counts: bool = False):
    """Delta-stepping SSSP (Meyer & Sanders) on the advance plan pair.

    Distances are partitioned into buckets of width ``delta``
    (:func:`repro.sparse.advance.estimate_delta` from the plan's weight
    distribution when unset).  The outer loop processes the lowest bucket
    holding a vertex that still *needs relaxing*; the inner loop repeatedly
    relaxes only the **light** edges (weight <= delta) leaving that bucket
    until it stops changing — light chains can re-enter the current bucket,
    heavy ones cannot — then the **heavy** edges of everything the bucket
    settled are relaxed once.  Both loops are ``lax.while_loop``s over the
    same plan pair as Bellman-Ford: every relaxation is an ordinary
    direction-optimized advance restricted by the plan's delta split
    (``edges="light"``/``"heavy"``), so all six schedules, both execution
    paths and all three direction policies apply unchanged, and the
    measured-density push/pull switch runs *per bucket phase* (light
    phases measure light-out-edge density, heavy phases heavy density).

    The driver tracks "needs relaxing" explicitly (a vertex re-enters
    whenever its distance improves) and terminates only when no vertex
    does, so it reaches the exact same relaxation fixed point as
    Bellman-Ford — distances are **bit-identical** to :func:`sssp` for
    every ``delta``, even when f32 bucket arithmetic mis-bins a boundary
    distance (mis-binning costs a round, never a bit).  Requires positive
    weights, like every delta-stepping.

    ``compact=True`` (default) builds the plan with gather-compacted push
    windows sized from the direction threshold — the sparse bucket
    frontiers are exactly the regime frontier compaction exists for.
    Like ``schedule``/``num_blocks``/``path``, ``compact`` is an
    *inspector* parameter: with a prebuilt ``plan=`` the plan's own
    ``compact_capacity`` governs (rebuild or pass ``build_advance(...,
    compact=)`` to change it); only ``delta`` — a per-call algorithm
    parameter, not an inspector product — is reconciled onto a prebuilt
    plan via :meth:`~repro.sparse.advance.AdvancePlan.with_delta`.
    ``max_iters`` caps *outer* rounds (default ``V + 2``: a round settles
    its bucket, and the slack absorbs boundary-rounding re-entries); if
    the cap is ever exhausted with work remaining, a plain Bellman-Ford
    backstop loop finishes the leftover relaxations, so the bit-identity
    contract holds unconditionally — a bad cap costs rounds, never bits.
    ``return_direction_counts=True`` appends (push, pull) advance counts
    across all bucket phases, as in :func:`bfs`/:func:`sssp`.
    """
    _check_driver_direction(direction)
    if _wants_sharded(plan, mesh):
        _shard, splan = _resolve_sharded_plan(
            graph, plan, mesh, schedule, num_blocks, path,
            workload="advance_delta",
            delta=delta if delta is not None else "auto", compact=compact,
            shard_schedule=shard_schedule)
        return _shard.sharded_delta_stepping(
            splan, source, delta=delta, max_iters=max_iters,
            direction=direction,
            return_direction_counts=return_direction_counts)
    with telemetry.span("delta_stepping"):
        V = graph.num_vertices
        _validate_sources(source, V)
        with telemetry.span("delta_stepping.plan"):
            aplan = _resolve_plan(graph, plan, schedule, num_blocks, path,
                                  workload="advance_delta",
                                  delta=delta if delta is not None else "auto",
                                  compact=compact)
            if aplan.delta is None or (delta is not None
                                       and float(delta) != aplan.delta):
                aplan = aplan.with_delta(delta)
        max_outer = (V + 2) if max_iters is None else max_iters

        # Per-phase compaction capacity: a light-bucket advance can never
        # activate more atoms than the light edge set holds (that count is the
        # ceiling of the measured light density the carry tracks), so each
        # phase's static capacity is clamped to its own edge subset and sparse
        # bucket frontiers stream tighter gather-compacted windows.  The
        # executor's measured count still picks each advance's rung below
        # that capacity, or the masked fallback above it, so a mis-sized
        # capacity costs streamed volume, never bits.
        light_cap = heavy_cap = aplan.compact_capacity
        if aplan.compact_capacity is not None and aplan.num_edges:
            # numpy on the plan's own (concrete, inspector-built) degree array:
            # the whole driver may be wrapped in jax.jit, where a jnp.sum here
            # would become a tracer and could not size a static capacity
            light_edges = int(np.asarray(aplan.light_out_degrees).sum())
            heavy_edges = aplan.num_edges - light_edges
            light_cap = min(aplan.compact_capacity, max(light_edges, 1))
            heavy_cap = min(aplan.compact_capacity, max(heavy_edges, 1))
        with telemetry.span("delta_stepping.dispatch"):
            dist, counts = _delta_loop(
                aplan, jnp.asarray(source, jnp.int32),
                max_outer=int(max_outer), direction=direction,
                light_cap=light_cap, heavy_cap=heavy_cap)
    if return_direction_counts:
        return dist, counts
    return dist


@functools.partial(jax.jit, static_argnames=("max_outer", "direction",
                                             "light_cap", "heavy_cap"))
def _delta_loop(aplan: AdvancePlan, source: jax.Array, *, max_outer: int,
                direction: str, light_cap: Optional[int],
                heavy_cap: Optional[int]):
    """Delta-stepping bucket loops; returns ``(dist, (push, pull) counts)``.

    ``light_cap``/``heavy_cap`` are the light and heavy phases' push
    compaction capacities (``None`` where the plan compacts nothing).
    """
    V = aplan.num_vertices
    width = aplan.delta
    inner_cap = V + 1
    light_plan = aplan.with_compact_capacity(light_cap)
    heavy_plan = aplan.with_compact_capacity(heavy_cap)
    light_out = aplan.light_out_degrees
    heavy_out = aplan.out_degrees - light_out

    def _active(mask, out_deg):
        return jnp.sum(jnp.where(mask, out_deg, 0)).astype(jnp.int32)

    dist0 = jnp.full((V,), INF).at[source].set(0.0)
    needs0 = jnp.zeros((V,), bool).at[source].set(True)

    def outer_cond(state):
        i, _, needs, _ = state
        return jnp.logical_and(i < max_outer, needs.any())

    @jax.named_scope("delta.bucket")
    def outer_body(state):
        i, dist, needs, counts = state
        bucket = jnp.min(jnp.where(needs, _bucket_of(dist, width),
                                   _FAR_BUCKET))

        def inner_cond(s):
            j, dist, needs, _, _ = s
            in_bucket = jnp.logical_and(needs,
                                        _bucket_of(dist, width) == bucket)
            return jnp.logical_and(j < inner_cap, in_bucket.any())

        def inner_body(s):
            j, dist, needs, settled, counts = s
            frontier = jnp.logical_and(needs,
                                       _bucket_of(dist, width) == bucket)
            new_dist, used_push = _relax_directed(
                light_plan, direction, dist, frontier,
                _active(frontier, light_out), edges="light")
            improved = new_dist < dist
            needs = jnp.logical_or(jnp.logical_and(needs, ~frontier),
                                   improved)
            return (j + 1, new_dist, needs,
                    jnp.logical_or(settled, frontier),
                    counts.at[jnp.where(used_push, 0, 1)].add(1))

        _, dist, needs, settled, counts = jax.lax.while_loop(
            inner_cond, inner_body,
            (0, dist, needs, jnp.zeros((V,), bool), counts))

        # heavy phase: every vertex the bucket settled relaxes its heavy
        # out-edges once, with its final in-bucket distance.  Skipped
        # outright when the settled set has no heavy out-edges (e.g. a
        # width past the max weight — the Delta -> inf Bellman-Ford
        # degeneration must not pay a no-op advance per bucket).
        active_heavy = _active(settled, heavy_out)

        def heavy_phase(_):
            new_dist, used_push = _relax_directed(
                heavy_plan, direction, dist, settled, active_heavy,
                edges="heavy")
            return new_dist, counts.at[jnp.where(used_push, 0, 1)].add(1)

        new_dist, counts = jax.lax.cond(
            active_heavy > 0, heavy_phase, lambda _: (dist, counts),
            operand=None)
        needs = jnp.logical_or(needs, new_dist < dist)
        return (i + 1, new_dist, needs, counts)

    _, dist, needs, counts = jax.lax.while_loop(
        outer_cond, outer_body,
        (0, dist0, needs0, jnp.zeros((2,), jnp.int32)))

    # Convergence backstop: if the outer cap was exhausted with work left
    # (pathological f32 bucket re-entries can cost more rounds than the
    # slack), finish with plain frontier Bellman-Ford over ALL edges from
    # the leftover needs set — from any upper-bound state it reaches the
    # same fixed point in <= V rounds, so the bit-identity contract holds
    # *unconditionally*, never silently truncated.  In the normal case
    # needs is empty and this loop costs one predicate evaluation.
    def mop_cond(state):
        j, _, needs, _ = state
        return jnp.logical_and(j < V, needs.any())

    def mop_body(state):
        j, dist, needs, counts = state
        new_dist, used_push = _relax_directed(
            aplan, direction, dist, needs,
            _active(needs, aplan.out_degrees))
        return (j + 1, new_dist, new_dist < dist,
                counts.at[jnp.where(used_push, 0, 1)].add(1))

    _, dist, _, counts = jax.lax.while_loop(
        mop_cond, mop_body, (0, dist, needs, counts))
    return dist, counts


@functools.partial(jax.jit, static_argnames=("max_iters", "direction",
                                             "return_parents"))
def _bfs_loop(aplan: AdvancePlan, source: jax.Array, max_iters: int,
              direction: str, return_parents: bool):
    """Shared BFS while-loop (single-source; vmap-able over ``source``).

    The carry threads ``(iteration, depth, [parent], frontier,
    active_out_edges, push_iterations)`` — the active-edge count is the
    measured frontier density the ``"auto"`` direction switches on, and the
    push counter is what the drivers report as direction statistics.
    """
    V = aplan.num_vertices
    ids = jnp.arange(V, dtype=jnp.int32)
    source = jnp.asarray(source, jnp.int32)
    frontier0 = ids == source
    depth0 = jnp.where(frontier0, 0, -1).astype(jnp.int32)
    parent0 = jnp.full((V,), jnp.int32(-1))

    def cond(state):
        return jnp.logical_and(state[0] < max_iters, state[3].any())

    @jax.named_scope("bfs.level")
    def body(state):
        # parent rides the carry only when requested (a dead [V] buffer
        # per vmap lane otherwise); slot 2 is a scalar placeholder then
        i, depth, parent, frontier, active_edges, pushes = state
        if return_parents:
            # one advance does both jobs: cand >= 0 iff the destination has
            # an active in-edge, so the scatter-or sweep is redundant here
            cand, used_push = _directed(
                aplan, direction, active_edges,
                lambda: advance_src_argmin(aplan, frontier,
                                           direction="push"),
                lambda: advance_src_argmin(aplan, frontier,
                                           direction="pull"))
            newly = jnp.logical_and(cand >= 0, depth < 0)
            parent = jnp.where(newly, cand, parent)
        else:
            reached, used_push = _directed(
                aplan, direction, active_edges,
                lambda: advance_frontier(aplan, frontier, direction="push"),
                lambda: advance_frontier(aplan, frontier, direction="pull"))
            newly = jnp.logical_and(reached, depth < 0)
        with jax.named_scope("frontier"):
            depth = jnp.where(newly, i + 1, depth)
        return (i + 1, depth, parent, newly,
                _active_edge_count(aplan, newly),
                pushes + used_push.astype(jnp.int32))

    state = jax.lax.while_loop(
        cond, body, (0, depth0, parent0 if return_parents else jnp.int32(0),
                     frontier0, _active_edge_count(aplan, frontier0),
                     jnp.int32(0)))
    iters, depth = state[0], state[1]
    parent = state[2] if return_parents else parent0
    pushes = state[5]
    return depth, parent, jnp.stack([pushes,
                                     jnp.int32(iters) - pushes])


def bfs(graph: Graph, source: int, *, max_iters: Optional[int] = None,
        schedule: Schedule | str = "auto",
        num_blocks: Optional[int] = None,
        path: ExecutionPath | str = ExecutionPath.AUTO,
        plan: Optional[AdvancePlan] = None,
        mesh=None,
        shard_schedule: Optional[str] = None,
        return_parents: bool = False,
        direction: str = "auto",
        return_direction_counts: bool = False):
    """BFS depth labels [V] (-1 = unreached); same advance, unit weights.

    ``return_parents=True`` additionally returns parent pointers [V]
    (-1 at the source and unreached vertices): each newly reached vertex's
    parent is its smallest frontier in-neighbour — deterministic, unlike
    the GPU's atomic race, and checkable (``depth[parent[v]] ==
    depth[v] - 1``) — in either direction (min over the same id multiset).

    ``direction="auto"`` (default) is direction-optimizing: push while the
    measured frontier out-edge fraction is below the plan's threshold, pull
    above.  ``return_direction_counts=True`` appends an int32 ``[2]`` array
    ``(push_iterations, pull_iterations)`` to the result tuple — the
    benchmark/CI evidence that the switch actually exercised both
    directions.

    ``mesh`` (shard count, 1-axis :class:`~jax.sharding.Mesh`, or
    ``"auto"``) runs the traversal device-sharded — see
    :mod:`repro.sparse.shard`; depths and parents stay bit-identical for
    every boundary schedule (``shard_schedule``).
    """
    _check_driver_direction(direction)
    if _wants_sharded(plan, mesh):
        _shard, splan = _resolve_sharded_plan(graph, plan, mesh, schedule,
                                              num_blocks, path,
                                              shard_schedule=shard_schedule)
        return _shard.sharded_bfs(
            splan, source, max_iters=max_iters,
            return_parents=return_parents, direction=direction,
            return_direction_counts=return_direction_counts)
    with telemetry.span("bfs"):
        V = graph.num_vertices
        _validate_sources(source, V)
        max_iters = V if max_iters is None else max_iters
        with telemetry.span("bfs.plan"):
            aplan = _resolve_plan(graph, plan, schedule, num_blocks, path)
        with telemetry.span("bfs.dispatch"):
            depth, parent, counts = _bfs_loop(
                aplan, jnp.asarray(source, jnp.int32), int(max_iters),
                direction, return_parents)
    out = (depth,)
    if return_parents:
        out = out + (parent,)
    if return_direction_counts:
        out = out + (counts,)
    return out[0] if len(out) == 1 else out


def bfs_multi(graph: Graph, sources, *, max_iters: Optional[int] = None,
              schedule: Schedule | str = "auto",
              num_blocks: Optional[int] = None,
              path: ExecutionPath | str = ExecutionPath.AUTO,
              plan: Optional[AdvancePlan] = None,
              mesh=None,
              shard_schedule: Optional[str] = None,
              direction: str = "pull") -> jax.Array:
    """Batched multi-source BFS: depth labels ``[S, V]`` for ``sources[s]``.

    One plan pair serves the whole batch — the inspector runs once and
    ``jax.vmap`` maps the shared while-loop over per-source carries.  This
    is the multi-source traversal the plan-pair design exists for:
    topology inspection is per *graph*, not per source.

    Default direction is ``"pull"``, not ``"auto"``: under vmap the
    direction ``lax.cond`` lowers to a select that executes *both*
    branches for every batch lane, so measured-density switching costs
    push + pull per iteration — strictly worse than either fixed
    direction.  ``"auto"`` stays available for batch sizes small enough
    that result-identical semantics matter more than the double advance.

    ``mesh`` runs each lane device-sharded (``jax.vmap`` over the
    ``shard_map``-ed loop — the batch axis composes with the mesh axis).
    """
    _check_driver_direction(direction)
    if _wants_sharded(plan, mesh):
        _shard, splan = _resolve_sharded_plan(graph, plan, mesh, schedule,
                                              num_blocks, path,
                                              shard_schedule=shard_schedule)
        return _shard.sharded_bfs_multi(splan, sources, max_iters=max_iters,
                                        direction=direction)
    V = graph.num_vertices
    _validate_sources(sources, V, what="bfs_multi sources")
    max_iters = V if max_iters is None else max_iters
    aplan = _resolve_plan(graph, plan, schedule, num_blocks, path)
    sources = jnp.asarray(sources, jnp.int32)

    def run(src):
        depth, _, _ = _bfs_loop(aplan, src, int(max_iters), direction,
                                return_parents=False)
        return depth

    return jax.vmap(run)(sources)


def _pagerank_share(pr: jax.Array, outdeg: jax.Array) -> jax.Array:
    """Degree-normalized contribution vector (dangling rows emit zero)."""
    return jax.lax.optimization_barrier(
        jnp.where(outdeg > 0, pr / jnp.maximum(outdeg, 1.0), 0.0))


def _pagerank_update(contrib: jax.Array, dangling: jax.Array,
                     damping: float, V: int) -> jax.Array:
    """New rank vector from advance output, with rounding pinned per op.

    The naive one-liner ``(1-d)/V + d*(contrib + dangling/V)`` is
    fusion-sensitive: XLA forms FMAs differently depending on the
    surrounding compilation unit (eager op-by-op, a jitted body, a
    ``while_loop`` body, a vmapped lane inside a jitted serving step), so
    the same inputs round to ulp-different bits per context.  Every driver
    and the serving layer must agree bitwise, so each intermediate is
    pinned behind an ``optimization_barrier`` — forcing one individually
    rounded op sequence everywhere.  :func:`_pagerank_share` pins the
    share vector for the same reason.
    """
    contrib, dangling = jax.lax.optimization_barrier((contrib, dangling))
    total = jax.lax.optimization_barrier(contrib + dangling / V)
    scaled = jax.lax.optimization_barrier(damping * total)
    return (1.0 - damping) / V + scaled


def pagerank(graph: Graph, *, damping: float = 0.85, num_iters: int = 50,
             tol: float = 0.0,
             schedule: Schedule | str = "auto",
             num_blocks: Optional[int] = None,
             path: ExecutionPath | str = ExecutionPath.AUTO,
             plan: Optional[AdvancePlan] = None,
             mesh=None,
             shard_schedule: Optional[str] = None,
             direction: str = "auto") -> jax.Array:
    """Power-iteration PageRank [V] through the balanced advance.

    The per-iteration kernel is a full (unmasked) sum-combiner advance —
    structurally a pull-SpMV of the degree-normalized adjacency, which is
    exactly the paper's point: graph analytics and sparse linear algebra
    share one load-balancing abstraction.  Dangling mass (zero out-degree
    vertices) is redistributed uniformly; stops early when the L1 step
    change drops to ``tol``.

    The frontier is always full (density 1.0), so ``direction="auto"``
    resolves to pull at build time — no per-iteration switch to pay for.
    ``direction="push"`` runs the scatter form instead (summation order
    differs, so expect ulp-level float differences, not bit-identity).

    ``mesh`` runs the iteration device-sharded (pull contributions stay
    per-destination reductions over the same atom segments; the dangling
    sum becomes a psum of per-shard partials).
    """
    _check_driver_direction(direction)
    direction = "pull" if direction == "auto" else direction
    if _wants_sharded(plan, mesh):
        _shard, splan = _resolve_sharded_plan(graph, plan, mesh, schedule,
                                              num_blocks, path,
                                              workload="reduce",
                                              shard_schedule=shard_schedule)
        return _shard.sharded_pagerank(splan, damping=damping,
                                       num_iters=num_iters, tol=tol,
                                       direction=direction)
    V = graph.num_vertices
    if V == 0:
        return jnp.zeros((0,), jnp.float32)
    with telemetry.span("pagerank"):
        # full-frontier sum-advance: no mask load/select per atom, so "auto"
        # scores the plain "reduce" cost family, not the masked-advance one
        with telemetry.span("pagerank.plan"):
            aplan = _resolve_plan(graph, plan, schedule, num_blocks, path,
                                  workload="reduce")
        outdeg = graph.out_degrees().astype(jnp.float32)
        with telemetry.span("pagerank.dispatch"):
            return _pagerank_loop(aplan, outdeg, damping=float(damping),
                                  num_iters=int(num_iters), tol=float(tol),
                                  direction=direction)


# The loop runs under jit, not eagerly: XLA lowers the sum-advance's
# reduction differently for an eagerly dispatched while_loop than for a
# jit-compiled one (even with the barrier-pinned update), and the serving
# layer's jitted step must reproduce driver bits exactly.  Compiling here
# puts both in the same regime (see serve/graph.py).  The plan is an
# argument, not a closure, so its arrays are not baked in as constants.
@functools.partial(jax.jit, static_argnames=("damping", "num_iters", "tol",
                                             "direction"))
def _pagerank_loop(aplan: AdvancePlan, outdeg: jax.Array, *, damping: float,
                   num_iters: int, tol: float, direction: str) -> jax.Array:
    V = aplan.num_vertices
    src = aplan.push_src if direction == "push" else aplan.src

    def cond(state):
        i, _, delta = state
        return jnp.logical_and(i < num_iters, delta > tol)

    @jax.named_scope("pagerank.iter")
    def body(state):
        i, pr, _ = state
        share = _pagerank_share(pr, outdeg)
        with jax.named_scope("gather"):
            shares = lane_take(share, src)
        if direction == "push":
            contrib = advance_push(aplan, None, shares, combiner="sum")
        else:
            contrib = advance(aplan, None, shares, combiner="sum")
        with jax.named_scope("update"):
            dangling = jnp.sum(jnp.where(outdeg > 0, 0.0, pr))
            new_pr = _pagerank_update(contrib, dangling, damping, V)
        return i + 1, new_pr, jnp.abs(new_pr - pr).sum()

    pr0 = jnp.full((V,), 1.0 / V, jnp.float32)
    _, pr, _ = jax.lax.while_loop(cond, body,
                                  (0, pr0, jnp.float32(jnp.inf)))
    return pr
