"""Sharded graph advance: recursive load balancing across devices.

The paper's hierarchy balances atoms over tiles and tiles over blocks; this
module adds the next level of the same recursion — **vertices over shards**
(devices on a 1-axis ``"shard"`` mesh).  Each shard owns a contiguous vertex
range and holds *local* pull/push CSR views of exactly its own rows, built
by the very same view-level inspector the single-device plan pair uses
(:func:`repro.sparse.advance.build_advance_views`): chunks balance blocks,
blocks balance shards, one cost model and autotune family per level
(``workload="advance_sharded"``, see
:func:`repro.core.autotune.select_sharded_plan` and
:func:`repro.core.balance.modeled_sharded_cost`).

Where the split points fall is itself a pluggable schedule — the
*boundary* schedules in :data:`SHARD_SCHEDULES`, the shard-level analogue
of the block-level balancing schedules:

* ``"equal_width"`` — uniform ``ceil(V/S)`` ranges (the thread-mapped
  schedule one level up; the default and the bitwise-frozen baseline);
* ``"edge_balanced"`` — split points from the prefix sum of each vertex's
  in+out degree (nonzero_split / merge-path one level up);
* ``"lpt_contiguous"`` — greedy nudging of edge-balanced boundaries that
  minimizes the max-shard load (LPT's move-work-off-the-max discipline,
  constrained to contiguous ranges).

Boundaries are always contiguous — that is what preserves per-destination
atom order and with it the bitwise contract below — but shards are no
longer uniform width: every local view is padded to the *max* shard width
and each shard's real extent rides the plan (``shard_lo``/``shard_hi``).

Execution contract (what makes the sharded result **bit-identical** to the
single-device plan, asserted by ``tests/test_shard_advance.py``):

* Shards own contiguous vertex ranges, so each local view is a contiguous
  *slice* of the global CSR with rebased offsets — every destination's atom
  segment survives in the same order, and the per-tile reductions reduce
  the same operands in the same order as one device would.
* State inside ``shard_map`` lives in **padded-slot coordinates**: vertex
  ``v`` owned by shard ``s`` occupies slot ``s * shard_size + (v - lo_s)``.
  The plan's ``glob2pad``/``pad2glob`` permutation maps between the two
  layouts; all per-atom source/destination index arrays are pre-mapped to
  padded coordinates at build time, so the gathered halo is indexed
  directly and the push combine scatters directly — no per-iteration
  relayout.  The map is monotone in global id (contiguity again), so
  min-reductions over ids (BFS parents) pick the same winner in either
  coordinate system.  For ``equal_width`` the permutation is the
  identity, which is what keeps the default byte-identical to the
  pre-boundary-schedule layout.
* The **pull** direction is purely local: a shard's tiles (destinations)
  own all their in-edge atoms, so
  :func:`repro.core.execute.execute_sharded_tile_reduce` needs no
  collective.  The frontier/state *halo* arrives first, via one
  ``all_gather`` of the ``[shard_size]`` carries per iteration.
* The **push** direction scatters anywhere: each shard produces a full
  ``[V_pad]`` partial (identity at untouched destinations) and
  :func:`repro.core.execute.execute_sharded_scatter_reduce` combines the
  partials with the combiner's matching collective (exact for min/max,
  disjoint-support-exact for sum), then each shard keeps its own slice.
* Ragged local edge counts are padded to a common ``E_max`` per direction
  **before** partitioning, so every shard traces the same shapes; padding
  atoms live in a dedicated pad tile past the owned rows and are masked
  out of every advance (``pull_valid``/``push_valid`` ride the plan), and
  padding *slots* of the carry only ever receive combiner identities, so
  they stay inert without extra masking.
* Direction choice is *global*: the measured frontier out-edge count is a
  ``psum`` across shards, compared against the plan's one modeled
  threshold — shards never disagree about direction, which keeps the
  ``lax.cond`` predicate uniform across the mesh.

Termination predicates (``frontier.any()`` etc.) must not issue collectives
inside ``while_loop`` *cond* functions, so every driver threads the psum'd
scalars (frontier population, active out-edge count) through its carry and
conds read the carry only.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import (ExecutionPath, Schedule, choose_execution_path,
                        estimate_compact_capacity,
                        estimate_direction_threshold,
                        execute_sharded_scatter_reduce,
                        execute_sharded_tile_reduce, make_partition)
from repro.core.autotune import (Plan, REGISTERED_PLANS, ShardedPlan,
                                 select_sharded_plan)
from repro.core.work import WorkSpec
from repro.launch.mesh import make_graph_mesh
from repro.sparse.advance import (DEFAULT_NUM_BLOCKS, AdvancePlan,
                                  _CHUNK_POLICIES, _combined_mask,
                                  build_advance_views, estimate_delta)
from repro.sparse.graph import (INF, _FAR_BUCKET, _SSSP_ALGORITHMS,
                                _bucket_of, _check_driver_direction,
                                _pagerank_share, _pagerank_update,
                                _validate_sources)

__all__ = ["SHARD_SCHEDULES", "ShardedAdvancePlan", "build_sharded_advance",
           "shard_boundaries", "sharded_bfs", "sharded_bfs_multi",
           "sharded_delta_stepping", "sharded_pagerank", "sharded_sssp"]


# ---------------------------------------------------------------------------
# Inspector: local views, uniform statics, stacking
# ---------------------------------------------------------------------------

def _local_csr_view(row_offsets, col_indices, values, lo: int, hi: int,
                    shard_size: int, e_max: int, *,
                    spread_pad: bool = False):
    """One shard's padded local view of a global CSR.

    Rows ``[lo, hi)`` of the global matrix become local tiles ``[0, hi-lo)``
    (trailing tiles up to ``shard_size`` are empty for a short final shard);
    tile ``shard_size`` is a dedicated *pad tile* holding the padding atoms
    ``[E_local, e_max)``.  Columns/values are the contiguous global slice —
    same per-row atom order as the global CSR, which is the bitwise
    contract.  Returns ``(offsets [shard_size+2], cols, vals, valid)``.

    ``spread_pad`` distributes the padding atoms evenly over the empty
    trailing slots *and* the pad tile instead of dumping them all into the
    pad tile.  Padding atoms are masked either way, so placement never
    changes results — but one huge pad segment inflates the blocked
    executor's static window/local-tile maxima (a merge-path block swallows
    the whole run of zero-atom slots, and another the monolithic pad
    segment), and the mesh-uniform statics impose that worst block shape
    on every shard.  Uneven boundary schedules (which create wide empty
    slot runs on their narrow shards) pay a multiple of the advance cost
    for it; ``equal_width`` keeps the legacy all-in-pad-tile layout
    byte-for-byte.
    """
    roff = np.asarray(row_offsets)
    lo = min(lo, hi)
    a0, a1 = int(roff[lo]), int(roff[hi])
    e_local = a1 - a0
    counts = np.diff(roff[lo:hi + 1])
    if spread_pad:
        n_bins = shard_size - counts.size + 1
        base, rem = divmod(e_max - e_local, n_bins)
        pad_counts = np.full(n_bins, base, np.int64)
        pad_counts[:rem] += 1
        offs = np.concatenate(
            [[0], np.cumsum(np.concatenate([counts, pad_counts]))]
        ).astype(np.int32)
    else:
        counts = np.concatenate(
            [counts, np.zeros(shard_size - counts.size, np.int64)])
        offs = np.concatenate(
            [[0], np.cumsum(counts), [e_max]]).astype(np.int32)
    cols = np.zeros(e_max, np.int32)
    vals = np.zeros(e_max, np.float32)
    valid = np.zeros(e_max, bool)
    cols[:e_local] = np.asarray(col_indices)[a0:a1]
    vals[:e_local] = np.asarray(values)[a0:a1]
    valid[:e_local] = True
    return offs, cols, vals, valid


def _shard_ranges(num_vertices: int, num_shards: int, shard_size: int):
    los = [s * shard_size for s in range(num_shards)]
    his = [min(lo + shard_size, num_vertices) for lo in los]
    return [(min(lo, hi), hi) for lo, hi in zip(los, his)]


def _direction_e_max(row_offsets, ranges) -> int:
    roff = np.asarray(row_offsets)
    return max(1, max(int(roff[hi] - roff[lo]) for lo, hi in ranges))


# ---------------------------------------------------------------------------
# Boundary schedules: where the contiguous split points fall
# ---------------------------------------------------------------------------

def _vertex_loads(fwd_row_offsets, rev_row_offsets):
    """Per-vertex work measure the degree-aware schedules balance.

    In + out degree (each edge is relaxed once per direction a traversal
    might take) plus 1 — the merge-path measure one level down counts a
    tile *and* its atoms, and the +1 keeps long edgeless stretches from
    collapsing into a single shard's range.
    """
    fdeg = np.diff(np.asarray(fwd_row_offsets).astype(np.int64))
    rdeg = np.diff(np.asarray(rev_row_offsets).astype(np.int64))
    return fdeg + rdeg + 1


def _equal_width_boundaries(loads, num_vertices, num_shards):
    width = max(-(-num_vertices // num_shards) if num_vertices else 1, 1)
    return np.minimum(
        np.arange(num_shards + 1, dtype=np.int64) * width, num_vertices)


def _edge_balanced_boundaries(loads, num_vertices, num_shards):
    # nonzero_split one level up: boundary k lands where the cumulative
    # load first reaches k/S of the total — searchsorted on the prefix sum,
    # exactly the merge-path diagonal intersection over (vertices, work).
    cum = np.concatenate([[0], np.cumsum(loads)])
    targets = cum[-1] * np.arange(1, num_shards, dtype=np.float64) / num_shards
    inner = np.searchsorted(cum, targets, side="left")
    bounds = np.concatenate([[0], inner, [num_vertices]]).astype(np.int64)
    return np.maximum.accumulate(np.minimum(bounds, num_vertices))


def _lpt_contiguous_boundaries(loads, num_vertices, num_shards):
    # LPT's move-work-off-the-max discipline under a contiguity constraint:
    # start from the edge-balanced split, then coordinate-descend each
    # interior boundary to the position minimizing max(left, right) load of
    # its two neighbours, sweeping until no boundary moves.
    bounds = _edge_balanced_boundaries(loads, num_vertices, num_shards)
    cum = np.concatenate([[0], np.cumsum(loads)])

    def seg(a, b):
        return cum[b] - cum[a]

    for _ in range(2 * num_shards):
        moved = False
        for k in range(1, num_shards):
            lo, hi = bounds[k - 1], bounds[k + 1]
            mid = (cum[lo] + cum[hi]) / 2.0
            x = int(np.clip(np.searchsorted(cum, mid, side="left"), lo, hi))
            best = bounds[k]
            best_cost = max(seg(lo, best), seg(best, hi))
            for cand in (x - 1, x, x + 1):
                if lo <= cand <= hi:
                    cost = max(seg(lo, cand), seg(cand, hi))
                    if cost < best_cost:
                        best, best_cost = cand, cost
            if best != bounds[k]:
                bounds[k] = best
                moved = True
        if not moved:
            break
    return bounds


#: The shard-level schedule registry — the analogue of the block-level
#: ``Schedule`` enum, one recursion up: each entry maps per-vertex loads to
#: the ``[S+1]`` contiguous boundary array ``build_sharded_advance`` splits
#: the vertex range on.  Order matters: auto-selection dedups identical
#: splits keeping the *first* name, so ``equal_width`` (the bitwise-frozen
#: baseline) wins ties.
SHARD_SCHEDULES = {
    "equal_width": _equal_width_boundaries,
    "edge_balanced": _edge_balanced_boundaries,
    "lpt_contiguous": _lpt_contiguous_boundaries,
}


def _validate_boundaries(bounds, num_vertices, num_shards, name):
    b = np.asarray(bounds, dtype=np.int64)
    if (b.shape != (num_shards + 1,) or b[0] != 0 or b[-1] != num_vertices
            or np.any(np.diff(b) < 0)):
        raise ValueError(
            f"shard schedule {name!r} produced invalid boundaries "
            f"{b.tolist()} for V={num_vertices}, S={num_shards}: need a "
            f"non-decreasing [S+1] split of [0, V]")
    return b


def _schedule_boundaries(fwd_csr, rev_csr, num_vertices, num_shards, name):
    if name not in SHARD_SCHEDULES:
        raise ValueError(f"unknown shard schedule {name!r} (expected one "
                         f"of {sorted(SHARD_SCHEDULES)})")
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if num_shards > max(num_vertices, 1) and name != "equal_width":
        raise ValueError(
            f"shard schedule {name!r} cannot split V={num_vertices} "
            f"vertices into S={num_shards} contiguous non-degenerate "
            f"shards; only 'equal_width' accepts a mesh larger than the "
            f"graph (its trailing shards are all-empty padding)")
    loads = _vertex_loads(fwd_csr.row_offsets, rev_csr.row_offsets)
    bounds = SHARD_SCHEDULES[name](loads, num_vertices, num_shards)
    return _validate_boundaries(bounds, num_vertices, num_shards, name)


def shard_boundaries(graph, num_shards: int,
                     shard_schedule: str = "equal_width"):
    """The ``[S+1]`` contiguous vertex boundaries a shard schedule yields.

    Public inspection hook for tests and benchmarks; the same computation
    :func:`build_sharded_advance` runs internally.
    """
    fwd = graph.csr
    return _schedule_boundaries(fwd, fwd.transpose(), graph.num_vertices,
                                int(num_shards), shard_schedule)


def _boundary_permutation(bounds, shard_size: int):
    """The global<->padded-slot bijection for a boundary array.

    Slot ``s * shard_size + j`` holds global vertex ``bounds[s] + j`` for
    ``j < width_s``; the remaining padding slots take the overflow ids
    ``[V, V_pad)`` in increasing order, making both maps full permutations
    of ``[0, V_pad)``.  For equal-width boundaries this is the identity —
    the property that keeps the default layout byte-identical to the
    pre-boundary-schedule one.
    """
    bounds = np.asarray(bounds, dtype=np.int64)
    num_shards = bounds.size - 1
    num_vertices = int(bounds[-1])
    v_pad = num_shards * shard_size
    pad2glob = np.empty(v_pad, dtype=np.int32)
    overflow = num_vertices
    for s in range(num_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        base = s * shard_size
        pad2glob[base:base + (hi - lo)] = np.arange(lo, hi, dtype=np.int32)
        n_pad = shard_size - (hi - lo)
        pad2glob[base + (hi - lo):base + shard_size] = np.arange(
            overflow, overflow + n_pad, dtype=np.int32)
        overflow += n_pad
    glob2pad = np.empty(v_pad, dtype=np.int32)
    glob2pad[pad2glob] = np.arange(v_pad, dtype=np.int32)
    return glob2pad, pad2glob


def _pull_shard_specs(rev_csr, num_vertices: int, num_shards: int):
    """Per-shard padded pull work views for one candidate shard count —
    the inputs :func:`repro.core.autotune.select_sharded_plan` scores."""
    shard_size = max(-(-num_vertices // num_shards) if num_vertices else 1, 1)
    ranges = _shard_ranges(num_vertices, num_shards, shard_size)
    e_max = _direction_e_max(rev_csr.row_offsets, ranges)
    specs = []
    for lo, hi in ranges:
        offs, _, _, _ = _local_csr_view(rev_csr.row_offsets,
                                        rev_csr.col_indices, rev_csr.values,
                                        lo, hi, shard_size, e_max)
        specs.append(WorkSpec.from_segment_offsets(jnp.asarray(offs),
                                                   num_atoms=e_max))
    return specs


def _candidate_shard_counts(num_vertices: int):
    """Powers of two up to the smaller of device count and vertex count."""
    n = max(len(jax.devices()), 1)
    counts, c = [], 1
    while c <= n and c <= max(num_vertices, 1):
        counts.append(c)
        c *= 2
    return counts


def _uniform_partitions(parts):
    """Rewrite per-shard partitions to share one set of static hints.

    ``shard_map`` traces a single program, so the statics baked into the
    executors' shapes (window spans, per-block item bound, chunk-queue
    width, the tile-aligned flag) must agree across shards.  Every
    uniformization direction is mask-safe: larger windows only add masked
    slots, ``tile_aligned=False`` on an aligned partition just runs the
    (identity-combining) fixup path, and zero-padded chunk queue columns
    are past each block's chunk count.
    """
    def _max_opt(vals):
        return None if any(v is None for v in vals) else max(vals)

    aspan = _max_opt([p.atom_span for p in parts])
    tspan = _max_opt([p.tile_span for p in parts])
    items = _max_opt([p.items_per_block for p in parts])
    items = items if items is None else int(items)
    aligned = all(p.tile_aligned for p in parts)
    out = []
    for p in parts:
        bc = p.block_chunks
        if bc is not None:
            wmax = max(q.block_chunks.shape[1] for q in parts)
            bc = jnp.pad(bc, ((0, 0), (0, wmax - bc.shape[1])))
        out.append(dataclasses.replace(
            p, atom_span=aspan, tile_span=tspan, items_per_block=items,
            tile_aligned=aligned, block_chunks=bc))
    return out


def _stack_tree(objs):
    """Stack pytrees leaf-wise; asserts identical treedefs (= statics)."""
    flats = [jax.tree_util.tree_flatten(o) for o in objs]
    td0 = flats[0][1]
    for _, td in flats[1:]:
        if td != td0:
            raise ValueError(
                f"shard statics diverged after uniformization: {td} != {td0}")
    return tuple(jnp.stack(ls) for ls in zip(*(f[0] for f in flats))), td0


# ---------------------------------------------------------------------------
# The sharded plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedAdvancePlan:
    """Inspector output for the device-sharded advance: one
    :class:`~repro.sparse.advance.AdvancePlan` *per shard*, stored stacked.

    ``template`` is shard 0's plan carrying the (uniform) statics —
    schedule, paths, threshold, compaction capacity, padded shapes; the
    per-shard arrays and partition/work-view leaves are stacked along a
    leading ``[num_shards]`` axis and fed through ``shard_map`` with
    ``P("shard")`` specs, where each shard reconstructs its local plan
    (:func:`_local_plan`).  Built outside jit, like every inspector
    product.

    State arrays the drivers shard are length ``V_pad = num_shards *
    shard_size`` in **padded-slot layout** (shard ``s``'s owned window at
    ``[s * shard_size, s * shard_size + width_s)``, padding slots after);
    :meth:`to_global` reorders results to global vertex order and trims to
    ``[:num_vertices]`` on the way out — the identity + slice for
    ``equal_width`` boundaries.
    """

    mesh: Mesh
    axis: str
    num_shards: int
    num_vertices: int         # global V, pre-padding
    shard_size: int           # max shard width (uneven boundaries pad up)
    num_edges: int            # global edge count (NOT the padded E_max)
    template: AdvancePlan
    arrays: dict              # stacked [S, ...] per-shard plan arrays
    pull_part_leaves: tuple
    pull_part_treedef: object
    push_part_leaves: tuple
    push_part_treedef: object
    pull_spec_leaves: tuple
    pull_spec_treedef: object
    push_spec_leaves: tuple
    push_spec_treedef: object
    shard_schedule: str = "equal_width"
    boundaries: tuple = ()    # [S+1] contiguous vertex split points
    glob2pad: Optional[jax.Array] = None   # [V_pad] global id -> slot
    pad2glob: Optional[jax.Array] = None   # [V_pad] slot -> global id

    @property
    def padded_vertices(self) -> int:
        return self.num_shards * self.shard_size

    def to_global(self, padded: jax.Array) -> jax.Array:
        """Reorder a padded-layout ``[..., V_pad]`` result to global vertex
        order, trimmed to ``[..., V]``.  An identity gather + slice for
        ``equal_width`` boundaries."""
        return jnp.take(padded, self.glob2pad[:self.num_vertices], axis=-1)

    @property
    def direction_threshold(self) -> float:
        return self.template.direction_threshold

    @property
    def delta(self) -> Optional[float]:
        return self.template.delta

    @property
    def schedule(self) -> Schedule:
        return self.template.schedule

    @property
    def path(self) -> ExecutionPath:
        return self.template.path

    def edge_fraction(self, active_edge_count: jax.Array) -> jax.Array:
        """Measured *global* frontier density: psum'd active out-edge count
        over the true global edge count.  The template's own ``num_edges``
        is the padded per-shard ``E_max`` — never use it here."""
        return active_edge_count.astype(jnp.float32) / jnp.float32(
            max(self.num_edges, 1))

    def data(self) -> dict:
        """The stacked pytree a ``shard_map`` body consumes: per-shard
        leaves under ``P(axis)``, plus the replicated global<->padded
        permutation under ``"glob"`` (see :func:`_data_specs`)."""
        return {"arrays": dict(self.arrays),
                "pull_part": list(self.pull_part_leaves),
                "push_part": list(self.push_part_leaves),
                "pull_spec": list(self.pull_spec_leaves),
                "push_spec": list(self.push_spec_leaves),
                "glob": {"glob2pad": self.glob2pad,
                         "pad2glob": self.pad2glob}}

    def on_mesh(self) -> "ShardedAdvancePlan":
        """The same plan with every per-shard leaf placed on its own device
        of the mesh (and the permutation replicated), so no driver call
        moves the graph from one device to the others."""
        split = NamedSharding(self.mesh, P(self.axis))
        put = lambda tree: jax.device_put(tree, split)
        return dataclasses.replace(
            self, arrays=put(dict(self.arrays)),
            pull_part_leaves=put(self.pull_part_leaves),
            push_part_leaves=put(self.push_part_leaves),
            pull_spec_leaves=put(self.pull_spec_leaves),
            push_spec_leaves=put(self.push_spec_leaves),
            glob2pad=jax.device_put(self.glob2pad,
                                    NamedSharding(self.mesh, P())),
            pad2glob=jax.device_put(self.pad2glob,
                                    NamedSharding(self.mesh, P())))

    def with_delta(self, delta: Optional[float] = None) -> "ShardedAdvancePlan":
        """Attach the light/heavy bucket split to every shard.

        Width ``None`` estimates from the *valid* (non-padding) push
        weights — identical to the single-device estimate, since the valid
        atoms are exactly the global edge set.  Per-shard light out-degrees
        count only valid light atoms, binned over owned rows.
        """
        push_w = np.asarray(self.arrays["push_weight"])
        push_v = np.asarray(self.arrays["push_valid"])
        if delta is None:
            delta = estimate_delta(push_w[push_v])
        delta = float(delta)
        if not delta > 0.0:
            raise ValueError(f"delta must be positive, got {delta}")
        thr = np.float32(delta)
        light = np.asarray(self.arrays["weight"]) <= thr
        push_light = push_w <= thr
        light_outs = []
        for s in range(self.num_shards):
            spec = jax.tree_util.tree_unflatten(
                self.push_spec_treedef, [l[s] for l in self.push_spec_leaves])
            tids = np.asarray(spec.atom_tile_ids())
            light_outs.append(np.bincount(
                tids, weights=(push_light[s] & push_v[s]).astype(np.int64),
                minlength=self.shard_size + 1)[:self.shard_size])
        arrays = dict(self.arrays)
        arrays["light_mask"] = jnp.asarray(light)
        arrays["push_light_mask"] = jnp.asarray(push_light)
        arrays["light_out_degrees"] = jnp.asarray(
            np.stack(light_outs).astype(np.int32))
        template = dataclasses.replace(
            self.template, delta=delta,
            light_mask=arrays["light_mask"][0],
            push_light_mask=arrays["push_light_mask"][0],
            light_out_degrees=arrays["light_out_degrees"][0])
        return dataclasses.replace(self, template=template,
                                   arrays=arrays).on_mesh()


def _local_plan(splan: ShardedAdvancePlan, data):
    """Reconstruct this shard's AdvancePlan inside a ``shard_map`` body.

    Every leaf arrives with a leading length-1 shard axis; squeeze it and
    re-hang the arrays on the template (whose statics are uniform by
    construction).  Returns ``(plan, pull_valid, push_valid)`` — the valid
    masks are ANDed into every advance's edge mask so padding atoms never
    contribute.
    """
    def sq(leaves, td):
        return jax.tree_util.tree_unflatten(td, [l[0] for l in leaves])

    a = {k: v[0] for k, v in data["arrays"].items()}
    t = splan.template
    delta_fields = {}
    if t.delta is not None:
        delta_fields = {"light_mask": a["light_mask"],
                        "push_light_mask": a["push_light_mask"],
                        "light_out_degrees": a["light_out_degrees"]}
    lp = dataclasses.replace(
        t,
        spec=sq(data["pull_spec"], splan.pull_spec_treedef),
        push_spec=sq(data["push_spec"], splan.push_spec_treedef),
        part=sq(data["pull_part"], splan.pull_part_treedef),
        push_part=sq(data["push_part"], splan.push_part_treedef),
        src=a["src"], weight=a["weight"], dst=a["dst"],
        push_weight=a["push_weight"], push_src=a["push_src"],
        out_degrees=a["out_degrees"], **delta_fields)
    return lp, a["pull_valid"], a["push_valid"]


def _data_specs(axis: str) -> dict:
    """``in_specs`` tree for :meth:`ShardedAdvancePlan.data`: per-shard
    leaves split over the mesh axis, the global<->padded permutation
    replicated (every shard indexes the whole map)."""
    return {"arrays": P(axis), "pull_part": P(axis), "push_part": P(axis),
            "pull_spec": P(axis), "push_spec": P(axis), "glob": P()}


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------

def _resolve_schedule_enum(schedule) -> tuple[Schedule, Optional[str]]:
    policy = _CHUNK_POLICIES.get(str(schedule))
    return (Schedule.CHUNKED if policy else Schedule(schedule)), policy


def build_sharded_advance(graph, num_shards=None, *,
                          schedule: Schedule | str = "auto",
                          num_blocks: Optional[int] = None,
                          path: ExecutionPath | str = ExecutionPath.AUTO,
                          workload: str = "advance",
                          shard_schedule: Optional[str] = None,
                          direction_threshold: Optional[float] = None,
                          delta: Optional[float | str] = None,
                          compact: Optional[bool | int | float] = None,
                          measure=None) -> ShardedAdvancePlan:
    """Inspect a graph into a :class:`ShardedAdvancePlan`.

    ``num_shards`` accepts an int (shards = devices on a fresh 1-axis graph
    mesh, :func:`repro.launch.mesh.make_graph_mesh`), an existing 1-axis
    :class:`~jax.sharding.Mesh`, or ``None``/``"auto"`` — which asks
    :func:`repro.core.autotune.select_sharded_plan` to pick the shard count
    jointly with schedule, path, and boundary schedule over power-of-two
    candidate counts (the ``workload="advance_sharded"`` family, its own
    cache namespace).  With an explicit count and ``schedule="auto"`` the
    same selector picks (schedule, path, boundary) for that count; fully
    explicit arguments skip the autotuner entirely.

    ``shard_schedule`` names a boundary schedule from
    :data:`SHARD_SCHEDULES` (where the contiguous split points fall);
    ``None`` defaults to ``"equal_width"`` when everything else is
    explicit, and to joint auto-selection over all registered boundary
    schedules whenever the autotuner runs anyway.  Pass
    ``shard_schedule="auto"`` to force boundary selection even with an
    explicit count and schedule.

    The direction threshold is computed **once from the global work views**
    (the same call the single-device inspector makes) and handed to every
    shard, so direction policy is a global constant; likewise ``delta`` (a
    static bucket width) is estimated from the global weight distribution.
    Per-shard inspection then runs the ordinary
    :func:`~repro.sparse.advance.build_advance_views` on each shard's
    rebased CSR slices with overridden ``push_src`` (padded-layout source
    ids) and ``out_degrees`` (owned vertices only).
    """
    num_blocks = DEFAULT_NUM_BLOCKS if num_blocks is None else num_blocks
    V = graph.num_vertices
    fwd = graph.csr
    rev = fwd.transpose()

    mesh = None
    if isinstance(num_shards, Mesh):
        mesh = num_shards
        if len(mesh.axis_names) != 1:
            raise ValueError(f"sharded advance needs a 1-axis mesh, got "
                             f"axes {mesh.axis_names}")
        S = int(np.prod(list(mesh.shape.values())))
    elif num_shards is None or num_shards == "auto":
        S = None
    else:
        S = int(num_shards)
        if S < 1:
            raise ValueError(f"num_shards must be >= 1, got {S}")

    if shard_schedule is not None and shard_schedule != "auto" \
            and shard_schedule not in SHARD_SCHEDULES:
        raise ValueError(
            f"unknown shard schedule {shard_schedule!r} (expected one of "
            f"{sorted(SHARD_SCHEDULES)} or 'auto')")
    auto_sched = (str(schedule) not in _CHUNK_POLICIES
                  and Schedule(schedule) == Schedule.AUTO)
    auto_boundary = shard_schedule in (None, "auto")
    if S is None or auto_sched or shard_schedule == "auto":
        counts = [S] if S is not None else _candidate_shard_counts(V)
        bnames = (tuple(SHARD_SCHEDULES) if auto_boundary
                  else (shard_schedule,))
        bounds_by_count = {}
        for c in counts:
            per, seen = {}, set()
            for bname in bnames:
                if c > max(V, 1) and bname != "equal_width":
                    continue  # degree-aware splits reject S > V
                arr = _schedule_boundaries(fwd, rev, V, c, bname)
                key = tuple(int(x) for x in arr)
                if key in seen:
                    continue  # identical split: first (default) name wins
                seen.add(key)
                per[bname] = arr
            bounds_by_count[c] = per
        plans = REGISTERED_PLANS
        if not auto_sched:
            sched_enum, _ = _resolve_schedule_enum(schedule)
            plans = (tuple(p for p in REGISTERED_PLANS
                           if p.schedule == sched_enum)
                     or (Plan(sched_enum),))
        if ExecutionPath(path) != ExecutionPath.AUTO:
            plans = (tuple(p for p in plans
                           if p.path == ExecutionPath(path)) or plans)
        sp: ShardedPlan = select_sharded_plan(
            rev.workspec(), bounds_by_count, num_blocks,
            push_spec=fwd.workspec(), plans=plans, measure=measure)
        if S is None:
            S = sp.num_shards
        if auto_boundary:
            shard_schedule = sp.boundary
        if auto_sched:
            schedule = sp.schedule
            if ExecutionPath(path) == ExecutionPath.AUTO:
                path = sp.path
    boundary_name = ("equal_width" if shard_schedule in (None, "auto")
                     else shard_schedule)

    if mesh is None:
        mesh = make_graph_mesh(S)
    axis = mesh.axis_names[0]

    bounds = _schedule_boundaries(fwd, rev, V, S, boundary_name)
    ranges = list(zip(bounds[:-1].tolist(), bounds[1:].tolist()))
    shard_size = max(max(hi - lo for lo, hi in ranges), 1)
    V_pad = S * shard_size
    glob2pad, pad2glob = _boundary_permutation(bounds, shard_size)
    e_pull = _direction_e_max(rev.row_offsets, ranges)
    e_push = _direction_e_max(fwd.row_offsets, ranges)

    # Global direction threshold: exactly the single-device inspector's
    # computation over the global work views, so S=1 matches unsharded
    # plans bit-for-bit and S>1 shards never disagree about direction.
    sched_enum, policy = _resolve_schedule_enum(schedule)
    if direction_threshold is None:
        pull_spec_g = rev.workspec()
        push_spec_g = fwd.workspec()
        pull_part_g = make_partition(pull_spec_g, sched_enum, num_blocks,
                                     chunk_policy=policy or "lpt")
        push_part_g = make_partition(push_spec_g, sched_enum, num_blocks,
                                     chunk_policy=policy or "lpt")
        direction_threshold = estimate_direction_threshold(
            pull_spec_g, push_spec_g, num_blocks,
            pull_schedule=sched_enum, push_schedule=sched_enum,
            pull_path=str(choose_execution_path(pull_part_g,
                                                ExecutionPath(path))),
            push_path=str(choose_execution_path(push_part_g,
                                                ExecutionPath(path))),
            pull_part=pull_part_g, push_part=push_part_g)

    # Mesh-global compaction capacity: resolve ``compact`` once from the
    # *global* edge count — the same resolution
    # :func:`~repro.sparse.advance.build_advance_views` applies to the
    # whole-graph push view — and hand every shard the concrete slot count.
    # Resolving per shard would size capacities from the padded local
    # ``E_max``: uniform across shards only incidentally (every shard pads
    # to the same width) and drifting from single-device semantics for
    # fractional ``compact=``.  A global bound keeps ``compact=`` composing
    # with ``mesh=`` on every driver and makes the statics-agreement
    # assertion below structural; executors clamp the capacity to their
    # local window count at run time, so a bound above a shard's padded
    # edge count stays correct.
    if compact is None or compact is False:
        compact_resolved: Optional[int] = None
    elif compact is True:
        compact_resolved = estimate_compact_capacity(
            graph.num_edges, float(direction_threshold))
    elif isinstance(compact, float):
        if not 0.0 < compact <= 1.0:
            raise ValueError(f"compact fraction must be in (0, 1], "
                             f"got {compact}")
        compact_resolved = max(int(np.ceil(graph.num_edges * compact)), 1)
    else:
        if int(compact) < 1:
            raise ValueError(f"compact capacity must be >= 1 (or None/"
                             f"False to disable), got {compact}")
        compact_resolved = int(compact)

    shard_plans, pull_valids, push_valids = [], [], []
    spread_pad = boundary_name != "equal_width"
    fwd_roff = np.asarray(fwd.row_offsets)
    for lo, hi in ranges:
        poffs, pcols, pvals, pvalid = _local_csr_view(
            rev.row_offsets, rev.col_indices, rev.values, lo, hi,
            shard_size, e_pull, spread_pad=spread_pad)
        qoffs, qcols, qvals, qvalid = _local_csr_view(
            fwd.row_offsets, fwd.col_indices, fwd.values, lo, hi,
            shard_size, e_push, spread_pad=spread_pad)
        pull_spec = WorkSpec.from_segment_offsets(jnp.asarray(poffs),
                                                  num_atoms=e_pull)
        push_spec = WorkSpec.from_segment_offsets(jnp.asarray(qoffs),
                                                  num_atoms=e_push)
        # owned vertices' real out-degrees, independent of where the
        # padding atoms were binned (spread_pad puts them in empty slots)
        out_deg = np.zeros(shard_size, np.int32)
        out_deg[:hi - lo] = np.diff(fwd_roff[lo:hi + 1]).astype(np.int32)
        tids = np.asarray(push_spec.atom_tile_ids())
        # pad atoms: source 0 (masked anyway), destination the dropped
        # overflow row V_pad; real atoms carry *padded-layout* ids so the
        # halo gather and the collective push combine index the gathered
        # padded state directly (identity mapping for equal_width).
        push_src = np.where(qvalid,
                            glob2pad[np.where(qvalid, lo + tids, 0)],
                            0).astype(np.int32)
        push_dst = np.where(qvalid,
                            glob2pad[np.where(qvalid, qcols, 0)],
                            V_pad).astype(np.int32)
        pull_src = glob2pad[pcols].astype(np.int32)
        plan = build_advance_views(
            pull_spec=pull_spec, pull_src=jnp.asarray(pull_src),
            pull_weight=jnp.asarray(pvals),
            push_spec=push_spec, push_dst=jnp.asarray(push_dst),
            push_weight=jnp.asarray(qvals),
            push_src=jnp.asarray(push_src),
            num_vertices=V_pad, schedule=schedule, num_blocks=num_blocks,
            path=path, workload=workload,
            direction_threshold=float(direction_threshold),
            compact=compact_resolved,
            out_degrees=jnp.asarray(out_deg))
        shard_plans.append(plan)
        pull_valids.append(jnp.asarray(pvalid))
        push_valids.append(jnp.asarray(qvalid))

    statics = [(p.schedule, p.path, p.push_schedule, p.push_path,
                p.direction_threshold, p.compact_capacity)
               for p in shard_plans]
    if any(s != statics[0] for s in statics[1:]):
        raise AssertionError(f"per-shard plan statics diverged: {statics}")

    pull_parts = _uniform_partitions([p.part for p in shard_plans])
    push_parts = _uniform_partitions([p.push_part for p in shard_plans])
    shard_plans = [dataclasses.replace(p, part=a, push_part=b)
                   for p, a, b in zip(shard_plans, pull_parts, push_parts)]

    pull_part_leaves, pull_part_td = _stack_tree(pull_parts)
    push_part_leaves, push_part_td = _stack_tree(push_parts)
    pull_spec_leaves, pull_spec_td = _stack_tree(
        [p.spec for p in shard_plans])
    push_spec_leaves, push_spec_td = _stack_tree(
        [p.push_spec for p in shard_plans])
    arrays = {f: jnp.stack([getattr(p, f) for p in shard_plans])
              for f in ("src", "weight", "dst", "push_weight", "push_src",
                        "out_degrees")}
    arrays["pull_valid"] = jnp.stack(pull_valids)
    arrays["push_valid"] = jnp.stack(push_valids)
    # each shard's real extent (uneven under degree-aware boundaries):
    # drivers read their own [1] slice to mask padding slots of the carry.
    arrays["shard_lo"] = jnp.asarray(bounds[:-1], jnp.int32)
    arrays["shard_hi"] = jnp.asarray(bounds[1:], jnp.int32)

    splan = ShardedAdvancePlan(
        mesh=mesh, axis=axis, num_shards=S, num_vertices=V,
        shard_size=shard_size, num_edges=graph.num_edges,
        template=shard_plans[0], arrays=arrays,
        pull_part_leaves=pull_part_leaves, pull_part_treedef=pull_part_td,
        push_part_leaves=push_part_leaves, push_part_treedef=push_part_td,
        pull_spec_leaves=pull_spec_leaves, pull_spec_treedef=pull_spec_td,
        push_spec_leaves=push_spec_leaves, push_spec_treedef=push_spec_td,
        shard_schedule=boundary_name,
        boundaries=tuple(int(b) for b in bounds),
        glob2pad=jnp.asarray(glob2pad), pad2glob=jnp.asarray(pad2glob))
    if delta is not None:
        splan = splan.with_delta(None if delta == "auto" else float(delta))
    return splan.on_mesh()


# ---------------------------------------------------------------------------
# Shard-local advance ops (inside shard_map bodies)
# ---------------------------------------------------------------------------

def _pull_local(splan, lp, frontier_full, atom_fn, *, combiner, edge_mask):
    """Local pull advance -> this shard's [shard_size] owned slice."""
    atom_mask = _combined_mask(frontier_full, lp.src, edge_mask)
    out = execute_sharded_tile_reduce(
        lp.spec, lp.part, atom_fn, jnp.float32, axis_name=splan.axis,
        path=lp.path, combiner=combiner, atom_mask=atom_mask)
    return out[:splan.shard_size]


def _push_local(splan, lp, frontier_full, atom_fn, *, combiner, edge_mask):
    """Local push advance + cross-shard combine -> owned [shard_size]."""
    atom_mask = _combined_mask(frontier_full, lp.push_src, edge_mask)
    full = execute_sharded_scatter_reduce(
        lp.push_spec, lp.push_part, atom_fn, lp.dst, lp.num_vertices,
        jnp.float32, axis_name=splan.axis, path=lp.push_path,
        combiner=combiner, atom_mask=atom_mask,
        compact_capacity=lp.compact_capacity)
    lo = jax.lax.axis_index(splan.axis) * splan.shard_size
    return jax.lax.dynamic_slice(full, (lo,), (splan.shard_size,))


def _subset_mask(lp, direction: str, edges: str, valid):
    """Edge-subset mask ANDed with the shard's padding-validity mask."""
    em = lp.edge_set_mask(edges, direction)
    return valid if em is None else jnp.logical_and(valid, em)


def _directed_sharded(splan, direction: str, active_edges, push_fn, pull_fn):
    """Direction switch on *global* measured density (psum'd count)."""
    if direction == "push":
        return push_fn(), jnp.bool_(True)
    if direction == "pull":
        return pull_fn(), jnp.bool_(False)
    density = splan.edge_fraction(active_edges)
    use_push = density < jnp.float32(splan.direction_threshold)
    return (jax.lax.cond(use_push, lambda _: push_fn(), lambda _: pull_fn(),
                         operand=None), use_push)


def _relax_local(splan, lp, pvalid, qvalid, direction, dist_full,
                 frontier_full, active_edges, edges: str = "all"):
    """One direction-resolved local min-relax; returns (cand, used_push)."""
    def push():
        return _push_local(splan, lp, frontier_full,
                           dist_full[lp.push_src] + lp.push_weight,
                           combiner="min",
                           edge_mask=_subset_mask(lp, "push", edges, qvalid))

    def pull():
        return _pull_local(splan, lp, frontier_full,
                           dist_full[lp.src] + lp.weight, combiner="min",
                           edge_mask=_subset_mask(lp, "pull", edges, pvalid))

    return _directed_sharded(splan, direction, active_edges, push, pull)


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------
# Each driver runs its shard_map under jax.jit: called eagerly, shard_map
# dispatches its body op by op and compiles about a hundred small programs
# per call.

def _make_bfs_fn(splan: ShardedAdvancePlan, max_iters: int, direction: str,
                 return_parents: bool):
    """The shard_map'ed single-source BFS loop (vmap-able over source)."""
    n, axis = splan.shard_size, splan.axis

    def body_fn(data, src):
        lp, pvalid, qvalid = _local_plan(splan, data)
        slots = jax.lax.axis_index(axis) * n + jnp.arange(n, dtype=jnp.int32)
        frontier0 = slots == data["glob"]["glob2pad"][src]
        depth0 = jnp.where(frontier0, 0, -1).astype(jnp.int32)
        parent0 = jnp.full((n,), jnp.int32(-1))
        outdeg = lp.out_degrees

        def g_active(f_l):
            return jax.lax.psum(
                jnp.sum(jnp.where(f_l, outdeg, 0)).astype(jnp.int32), axis)

        def g_count(f_l):
            return jax.lax.psum(jnp.sum(f_l).astype(jnp.int32), axis)

        def cond(s):
            return jnp.logical_and(s[0] < max_iters, s[6] > 0)

        def body(s):
            i, depth, parent, frontier_l, active_edges, pushes, _ = s
            full_f = jax.lax.all_gather(frontier_l, axis, tiled=True)
            if return_parents:
                def push():
                    return _push_local(
                        splan, lp, full_f, lp.push_src.astype(jnp.float32),
                        combiner="min", edge_mask=qvalid)

                def pull():
                    return _pull_local(
                        splan, lp, full_f, lp.src.astype(jnp.float32),
                        combiner="min", edge_mask=pvalid)

                cand, used_push = _directed_sharded(
                    splan, direction, active_edges, push, pull)
                cand = jnp.where(jnp.isfinite(cand), cand,
                                 -1.0).astype(jnp.int32)
                newly = jnp.logical_and(cand >= 0, depth < 0)
                parent = jnp.where(newly, cand, parent)
            else:
                unit = lambda e: jnp.ones(e.shape, jnp.float32)

                def push():
                    return _push_local(splan, lp, full_f, unit,
                                       combiner="max", edge_mask=qvalid)

                def pull():
                    return _pull_local(splan, lp, full_f, unit,
                                       combiner="max", edge_mask=pvalid)

                reached, used_push = _directed_sharded(
                    splan, direction, active_edges, push, pull)
                newly = jnp.logical_and(reached > 0.0, depth < 0)
            depth = jnp.where(newly, i + 1, depth)
            return (i + 1, depth, parent, newly, g_active(newly),
                    pushes + used_push.astype(jnp.int32), g_count(newly))

        state = jax.lax.while_loop(
            cond, body,
            (0, depth0, parent0 if return_parents else jnp.int32(0),
             frontier0, g_active(frontier0), jnp.int32(0),
             g_count(frontier0)))
        iters, pushes = jnp.int32(state[0]), state[5]
        parent = state[2]
        if return_parents:
            # parents were min-reduced in padded-slot coordinates (monotone
            # in global id, so the winning edge is the same); hand the
            # caller global vertex ids.
            p2g = data["glob"]["pad2glob"]
            parent = jnp.where(parent >= 0,
                               p2g[jnp.maximum(parent, 0)], jnp.int32(-1))
        return state[1], parent, jnp.stack([pushes, iters - pushes])

    return jax.jit(jax.shard_map(
        body_fn, mesh=splan.mesh, in_specs=(_data_specs(axis), P()),
        out_specs=(P(axis), P(axis) if return_parents else P(), P()),
        check_vma=False))


def sharded_bfs(splan: ShardedAdvancePlan, source, *,
                max_iters: Optional[int] = None,
                return_parents: bool = False, direction: str = "auto",
                return_direction_counts: bool = False):
    """Sharded BFS; same contract (and bits) as :func:`repro.sparse.graph.bfs`."""
    _check_driver_direction(direction)
    V = splan.num_vertices
    _validate_sources(source, V)
    if return_parents and splan.padded_vertices >= (1 << 24):
        raise ValueError(
            f"sharded BFS parents reduce vertex ids as f32, exact only "
            f"below 2**24 padded vertices (got {splan.padded_vertices})")
    max_iters = V if max_iters is None else max_iters
    run = _make_bfs_fn(splan, max_iters, direction, return_parents)
    depth_pad, parent_pad, counts = run(splan.data(),
                                        jnp.asarray(source, jnp.int32))
    out = (splan.to_global(depth_pad),)
    if return_parents:
        out = out + (splan.to_global(parent_pad),)
    if return_direction_counts:
        out = out + (counts,)
    return out[0] if len(out) == 1 else out


def sharded_bfs_multi(splan: ShardedAdvancePlan, sources, *,
                      max_iters: Optional[int] = None,
                      direction: str = "pull") -> jax.Array:
    """Batched sharded BFS: ``jax.vmap`` over the shard_map'ed loop.

    Default direction pull, same rationale as the single-device driver —
    under vmap the direction ``lax.cond`` lowers to both-branch selects.
    """
    _check_driver_direction(direction)
    V = splan.num_vertices
    _validate_sources(sources, V, what="bfs_multi sources")
    max_iters = V if max_iters is None else max_iters
    run = _make_bfs_fn(splan, max_iters, direction, return_parents=False)
    data = splan.data()
    sources = jnp.asarray(sources, jnp.int32)
    depths = jax.vmap(lambda s: run(data, s)[0])(sources)
    return splan.to_global(depths)


def sharded_sssp(splan: ShardedAdvancePlan, source, *,
                 max_iters: Optional[int] = None, direction: str = "auto",
                 algorithm: str = "bellman_ford",
                 delta: Optional[float] = None,
                 return_direction_counts: bool = False):
    """Sharded SSSP; same contract (and bits) as :func:`repro.sparse.graph.sssp`."""
    _check_driver_direction(direction)
    if algorithm not in _SSSP_ALGORITHMS:
        raise ValueError(f"unknown algorithm: {algorithm!r} "
                         f"(expected one of {_SSSP_ALGORITHMS})")
    if algorithm == "delta":
        return sharded_delta_stepping(
            splan, source, delta=delta, max_iters=max_iters,
            direction=direction,
            return_direction_counts=return_direction_counts)
    V = splan.num_vertices
    _validate_sources(source, V)
    max_iters = V if max_iters is None else max_iters
    n, axis = splan.shard_size, splan.axis

    def body_fn(data, src):
        lp, pvalid, qvalid = _local_plan(splan, data)
        slots = jax.lax.axis_index(axis) * n + jnp.arange(n, dtype=jnp.int32)
        frontier0 = slots == data["glob"]["glob2pad"][src]
        dist0 = jnp.where(frontier0, 0.0, INF)
        outdeg = lp.out_degrees

        def g_active(f_l):
            return jax.lax.psum(
                jnp.sum(jnp.where(f_l, outdeg, 0)).astype(jnp.int32), axis)

        def g_count(f_l):
            return jax.lax.psum(jnp.sum(f_l).astype(jnp.int32), axis)

        def cond(s):
            return jnp.logical_and(s[0] < max_iters, s[5] > 0)

        def body(s):
            i, dist_l, frontier_l, active_edges, pushes, _ = s
            full_f = jax.lax.all_gather(frontier_l, axis, tiled=True)
            full_d = jax.lax.all_gather(dist_l, axis, tiled=True)
            cand, used_push = _relax_local(splan, lp, pvalid, qvalid,
                                           direction, full_d, full_f,
                                           active_edges)
            new_dist = jnp.minimum(dist_l, cand)
            new_frontier = new_dist < dist_l
            return (i + 1, new_dist, new_frontier, g_active(new_frontier),
                    pushes + used_push.astype(jnp.int32),
                    g_count(new_frontier))

        state = jax.lax.while_loop(
            cond, body, (0, dist0, frontier0, g_active(frontier0),
                         jnp.int32(0), g_count(frontier0)))
        iters, pushes = jnp.int32(state[0]), state[4]
        return state[1], jnp.stack([pushes, iters - pushes])

    run = jax.jit(jax.shard_map(body_fn, mesh=splan.mesh,
                                in_specs=(_data_specs(axis), P()),
                                out_specs=(P(axis), P()), check_vma=False))
    dist_pad, counts = run(splan.data(), jnp.asarray(source, jnp.int32))
    dist = splan.to_global(dist_pad)
    if return_direction_counts:
        return dist, counts
    return dist


def sharded_delta_stepping(splan: ShardedAdvancePlan, source, *,
                           delta: Optional[float] = None,
                           max_iters: Optional[int] = None,
                           direction: str = "auto",
                           return_direction_counts: bool = False):
    """Sharded delta-stepping; bit-identical to the single-device driver.

    Same nested-loop structure as :func:`repro.sparse.graph.delta_stepping`
    (light inner loop, one heavy relax per settled bucket, Bellman-Ford
    mop-up backstop), with every termination/bucket scalar made global:
    the active bucket is a ``pmin`` over shards, the in-bucket and
    needs-relaxing populations are psum'd counts threaded through the
    carries so the ``while_loop`` conds stay collective-free.
    """
    _check_driver_direction(direction)
    V = splan.num_vertices
    _validate_sources(source, V)
    if splan.delta is None or (delta is not None
                               and float(delta) != splan.delta):
        splan = splan.with_delta(delta)
    width = splan.delta
    max_outer = (V + 2) if max_iters is None else max_iters
    inner_cap = V + 1
    n, axis = splan.shard_size, splan.axis

    def body_fn(data, src):
        lp, pvalid, qvalid = _local_plan(splan, data)
        slots = jax.lax.axis_index(axis) * n + jnp.arange(n, dtype=jnp.int32)
        needs0 = slots == data["glob"]["glob2pad"][src]
        dist0 = jnp.where(needs0, 0.0, INF)
        light_out = lp.light_out_degrees
        heavy_out = lp.out_degrees - light_out

        def g_active(mask_l, deg_l):
            return jax.lax.psum(
                jnp.sum(jnp.where(mask_l, deg_l, 0)).astype(jnp.int32), axis)

        def g_count(mask_l):
            return jax.lax.psum(jnp.sum(mask_l).astype(jnp.int32), axis)

        def relax(dist_l, frontier_l, active, edges):
            full_f = jax.lax.all_gather(frontier_l, axis, tiled=True)
            full_d = jax.lax.all_gather(dist_l, axis, tiled=True)
            cand, used_push = _relax_local(splan, lp, pvalid, qvalid,
                                           direction, full_d, full_f,
                                           active, edges=edges)
            return jnp.minimum(dist_l, cand), used_push

        def outer_cond(s):
            return jnp.logical_and(s[0] < max_outer, s[4] > 0)

        def outer_body(s):
            i, dist_l, needs_l, counts, _ = s
            bucket = jax.lax.pmin(
                jnp.min(jnp.where(needs_l, _bucket_of(dist_l, width),
                                  _FAR_BUCKET)), axis)

            def inner_cond(t):
                return jnp.logical_and(t[0] < inner_cap, t[5] > 0)

            def inner_body(t):
                j, dist_l, needs_l, settled_l, counts, _ = t
                frontier_l = jnp.logical_and(
                    needs_l, _bucket_of(dist_l, width) == bucket)
                new_dist, used_push = relax(
                    dist_l, frontier_l, g_active(frontier_l, light_out),
                    "light")
                improved = new_dist < dist_l
                needs_l = jnp.logical_or(
                    jnp.logical_and(needs_l, ~frontier_l), improved)
                nxt = jnp.logical_and(needs_l,
                                      _bucket_of(new_dist, width) == bucket)
                return (j + 1, new_dist, needs_l,
                        jnp.logical_or(settled_l, frontier_l),
                        counts.at[jnp.where(used_push, 0, 1)].add(1),
                        g_count(nxt))

            in0 = jnp.logical_and(needs_l,
                                  _bucket_of(dist_l, width) == bucket)
            _, dist_l, needs_l, settled_l, counts, _ = jax.lax.while_loop(
                inner_cond, inner_body,
                (0, dist_l, needs_l, jnp.zeros((n,), bool), counts,
                 g_count(in0)))

            # heavy phase: unconditional — an empty settled frontier makes
            # the relax a no-op (identity everywhere), and skipping the
            # single-device driver's lax.cond keeps all collectives on the
            # unconditionally-traced path of the SPMD program.
            active_heavy = g_active(settled_l, heavy_out)
            new_dist, used_push = relax(dist_l, settled_l, active_heavy,
                                        "heavy")
            counts = jnp.where(
                active_heavy > 0,
                counts.at[jnp.where(used_push, 0, 1)].add(1), counts)
            needs_l = jnp.logical_or(needs_l, new_dist < dist_l)
            return (i + 1, new_dist, needs_l, counts, g_count(needs_l))

        _, dist_l, needs_l, counts, nneeds = jax.lax.while_loop(
            outer_cond, outer_body,
            (0, dist0, needs0, jnp.zeros((2,), jnp.int32),
             g_count(needs0)))

        def mop_cond(s):
            return jnp.logical_and(s[0] < V, s[4] > 0)

        def mop_body(s):
            j, dist_l, needs_l, counts, _ = s
            new_dist, used_push = relax(
                dist_l, needs_l, g_active(needs_l, lp.out_degrees), "all")
            new_needs = new_dist < dist_l
            return (j + 1, new_dist, new_needs,
                    counts.at[jnp.where(used_push, 0, 1)].add(1),
                    g_count(new_needs))

        _, dist_l, _, counts, _ = jax.lax.while_loop(
            mop_cond, mop_body, (0, dist_l, needs_l, counts, nneeds))
        return dist_l, counts

    run = jax.jit(jax.shard_map(body_fn, mesh=splan.mesh,
                                in_specs=(_data_specs(axis), P()),
                                out_specs=(P(axis), P()), check_vma=False))
    dist_pad, counts = run(splan.data(), jnp.asarray(source, jnp.int32))
    dist = splan.to_global(dist_pad)
    if return_direction_counts:
        return dist, counts
    return dist


def sharded_pagerank(splan: ShardedAdvancePlan, *, damping: float = 0.85,
                     num_iters: int = 50, tol: float = 0.0,
                     direction: str = "auto") -> jax.Array:
    """Sharded PageRank; matches :func:`repro.sparse.graph.pagerank`.

    Pull contributions are per-destination reductions over the same rebased
    atom segments as single-device, so pull results are bit-identical
    whenever the sums themselves are exactly representable; the dangling
    term is a psum of per-shard partial sums (order differs from a single
    device's one-pass sum, so general float graphs agree to tolerance, and
    dyadic constructions agree bitwise).  Padding rows are pinned to rank 0
    every iteration — they would otherwise absorb base/dangling mass and
    corrupt the real rows' next iteration.
    """
    _check_driver_direction(direction)
    direction = "pull" if direction == "auto" else direction
    V = splan.num_vertices
    if V == 0:
        return jnp.zeros((0,), jnp.float32)
    n, axis = splan.shard_size, splan.axis

    def body_fn(data):
        lp, pvalid, qvalid = _local_plan(splan, data)
        width = data["arrays"]["shard_hi"][0] - data["arrays"]["shard_lo"][0]
        is_real = jnp.arange(n, dtype=jnp.int32) < width
        outdeg = lp.out_degrees.astype(jnp.float32)
        pr0 = jnp.where(is_real, 1.0 / V, 0.0).astype(jnp.float32)

        def cond(s):
            return jnp.logical_and(s[0] < num_iters, s[2] > tol)

        def body(s):
            i, pr_l, _ = s
            share_l = _pagerank_share(pr_l, outdeg)
            full_share = jax.lax.all_gather(share_l, axis, tiled=True)
            if direction == "push":
                contrib = _push_local(splan, lp, None,
                                      full_share[lp.push_src],
                                      combiner="sum", edge_mask=qvalid)
            else:
                contrib = _pull_local(splan, lp, None, full_share[lp.src],
                                      combiner="sum", edge_mask=pvalid)
            dangling = jax.lax.psum(
                jnp.sum(jnp.where(outdeg > 0, 0.0, pr_l)), axis)
            new_pr = _pagerank_update(contrib, dangling, damping, V)
            new_pr = jnp.where(is_real, new_pr, 0.0)
            step = jax.lax.psum(jnp.abs(new_pr - pr_l).sum(), axis)
            return i + 1, new_pr, step

        _, pr_l, _ = jax.lax.while_loop(cond, body,
                                        (0, pr0, jnp.float32(jnp.inf)))
        return pr_l

    run = jax.jit(jax.shard_map(body_fn, mesh=splan.mesh,
                                in_specs=(_data_specs(axis),),
                                out_specs=P(axis), check_vma=False))
    return splan.to_global(run(splan.data()))
