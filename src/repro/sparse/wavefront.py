"""Topological wavefront scheduling: DAG/tree evaluation as a balanced
frontier workload.

Nothing in the frontier machinery requires graph *traversal*: dependency-
ordered computation over trees and DAGs (TreeLSTM-style recursive
evaluation, expression forests, task graphs) is the same abstraction with
the roles recast — **tiles = nodes, atoms = dependency in-edges**.  A
wavefront level is a frontier; the per-node work is a dense kernel
(:func:`repro.kernels.segmm.ops.level_grouped_matmul`) instead of a scalar
relax.  Atos (arXiv 2112.00132) drives exactly this wavefront-style
task-parallel dependency execution with the chunked-queue machinery this
repo already ships.

The scheduler generalizes delta-stepping's bucket loop: a node enters the
ready bucket when its **in-degree counter** — decremented by an ordinary
``advance`` over the dependency edges resolved each level — reaches zero.
Concretely, per iteration of a ``lax.while_loop`` shaped like the drivers
in :mod:`repro.sparse.graph`:

1. ``ready = (indeg == 0) & ~resolved`` — the current wavefront level;
2. the **dependency combine**: a pull advance (frontier = the resolved
   set) sums each node's already-evaluated predecessor states, one
   balanced advance per feature column under ``jax.vmap`` — any of the
   six schedules, either execution path, all bitwise-identical;
3. the **level GEMM**: every ready node's combined state hits its
   operator's weight matrix in ONE segmented matmul
   (:func:`~repro.kernels.segmm.ops.level_grouped_matmul`, grouped by
   op), committed under the ready mask — TreeLSTM-style recursion
   becomes one balanced GEMM per level instead of per-node calls;
4. the **counter decrement**: a unit-valued advance over the out-edges of
   the nodes that just resolved lowers the remaining in-degrees — next
   level's ready set emerges with no host round-trip.

The dependency CSR is inspected **once** by the ordinary
:func:`~repro.sparse.advance.build_advance` (``schedule="auto"`` routes
through the ``workload="wavefront"`` autotune family, its own cache
namespace and cost constants); acyclicity and the level count are
validated host-side at build time, so the device loop needs no cycle
guard.  Ragged forests batch through :mod:`repro.data.packing` into one
block-diagonal DAG (:func:`pack_forest`) — every tree's levels advance in
the same wavefront, which is the whole batching win.

Edge orientation: an edge ``u -> v`` in the dependency CSR means *u must
be evaluated before v* (for trees: children point at their parent).
Nodes with no in-edges are the wavefront's sources (level 0); a node's
in-degree is its dependency fan-in — the skew the schedules balance.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ExecutionPath, Schedule
from repro.kernels.segmm.ops import level_grouped_matmul
from repro.sparse.advance import AdvancePlan, advance, build_advance
from repro.sparse.formats import CSR
from repro.sparse.graph import Graph

#: Named activations (string spellings resolve here; callables pass
#: through).  ``relu`` and ``identity`` are exact in every backend — the
#: bitwise conformance matrix uses them (and bounded ``clip`` callables);
#: ``tanh`` is the model-quality choice and matches NumPy only to ULP.
ACTIVATIONS = {
    "relu": lambda z: jnp.maximum(z, 0.0),
    "tanh": jnp.tanh,
    "identity": lambda z: z,
}


def _resolve_activation(activation) -> Callable[[jax.Array], jax.Array]:
    if callable(activation):
        return activation
    try:
        return ACTIVATIONS[activation]
    except KeyError:
        raise ValueError(
            f"unknown activation: {activation!r} (expected a callable or "
            f"one of {sorted(ACTIVATIONS)})") from None


def topological_levels(row_offsets: np.ndarray, col_indices: np.ndarray,
                       num_nodes: int) -> np.ndarray:
    """Kahn-style level assignment over a dependency CSR (host-side).

    ``level_of[v]`` = length of the longest dependency chain ending at
    ``v`` (sources are level 0).  Raises :class:`ValueError` on cycles —
    the nodes whose counters never reach zero.  This is the inspector
    half of the wavefront contract: the device loop below replays exactly
    these levels from the in-degree counters, so the host result doubles
    as the oracle the property tests check the driver against.
    """
    row_offsets = np.asarray(row_offsets, np.int64)
    col_indices = np.asarray(col_indices, np.int64)
    indeg = np.zeros(num_nodes, np.int64)
    np.add.at(indeg, col_indices, 1)
    level_of = np.full(num_nodes, -1, np.int32)
    frontier = np.flatnonzero(indeg == 0)
    level = 0
    placed = 0
    while frontier.size:
        level_of[frontier] = level
        placed += frontier.size
        nxt = np.concatenate(
            [col_indices[row_offsets[u]:row_offsets[u + 1]]
             for u in frontier]) if frontier.size else col_indices[:0]
        np.subtract.at(indeg, nxt, 1)
        # a successor enters the next level when its LAST in-edge resolves;
        # restrict to successors of this level so each node appears once
        cand = np.unique(nxt)
        frontier = cand[indeg[cand] == 0]
        level += 1
    if placed != num_nodes:
        stuck = np.flatnonzero(level_of < 0)
        raise ValueError(
            f"dependency graph has a cycle: {stuck.size} of {num_nodes} "
            f"nodes can never become ready (e.g. nodes "
            f"{stuck[:8].tolist()}); wavefront scheduling needs a DAG")
    return level_of


@dataclasses.dataclass(frozen=True)
class WavefrontPlan:
    """One-time inspector product for a dependency DAG.

    ``plan`` is the ordinary :class:`~repro.sparse.advance.AdvancePlan`
    pair over the dependency CSR (pull view: tiles = nodes, atoms =
    in-edges — the mapping the whole module rests on).  ``level_of`` /
    ``num_levels`` / ``level_counts`` are the host-side Kahn products:
    build-time cycle validation, the while-loop's iteration bound, and
    the per-level node histogram the benchmarks report.
    """

    plan: AdvancePlan
    num_levels: int
    level_of: np.ndarray      # [V] int32 host-side (inspector product)
    level_counts: np.ndarray  # [num_levels] int64 nodes per level

    @property
    def num_nodes(self) -> int:
        return self.plan.num_vertices

    @property
    def num_dependencies(self) -> int:
        return self.plan.num_edges

    def in_degrees(self) -> jax.Array:
        """Dependency fan-in per node — the wavefront's ready counters
        (the pull view's atoms-per-tile array, by construction)."""
        return self.plan.spec.atoms_per_tile().astype(jnp.int32)


def build_wavefront(dag: Graph, *,
                    schedule: Schedule | str = "auto",
                    num_blocks: Optional[int] = None,
                    path: ExecutionPath | str = ExecutionPath.AUTO,
                    workload: str = "wavefront",
                    measure=None) -> WavefrontPlan:
    """Inspect a dependency DAG into a :class:`WavefrontPlan`.

    One call validates acyclicity (host-side Kahn leveling — a cycle
    raises here, at build time, never silently inside the device loop)
    and builds the dependency CSR's :class:`AdvancePlan` pair through the
    ordinary :func:`~repro.sparse.advance.build_advance` inspector.
    ``schedule="auto"`` scores the ``workload="wavefront"`` family (its
    push sibling ``"wavefront_push"`` prices the forward view), so the
    dependency combine's schedule is chosen by the same cost model as
    every other workload in the repo.
    """
    level_of = topological_levels(dag.csr.row_offsets, dag.csr.col_indices,
                                  dag.num_vertices)
    num_levels = int(level_of.max()) + 1 if level_of.size else 0
    plan = build_advance(dag, schedule=schedule, num_blocks=num_blocks,
                         path=path, workload=workload, measure=measure)
    counts = np.bincount(level_of, minlength=max(num_levels, 1)) \
        if level_of.size else np.zeros(0, np.int64)
    return WavefrontPlan(plan=plan, num_levels=num_levels,
                         level_of=level_of,
                         level_counts=counts[:num_levels].astype(np.int64))


def _validate_ops(op_of_node, num_ops: int, num_nodes: int) -> None:
    """Reject out-of-range operator ids at build time (concrete inputs
    only, like :func:`repro.sparse.graph._validate_sources`): under jit
    the level GEMM's block->op map clips silently, so a bad id would
    evaluate the wrong operator instead of failing."""
    if isinstance(op_of_node, jax.core.Tracer):
        return
    arr = np.asarray(op_of_node)
    if arr.shape != (num_nodes,):
        raise ValueError(f"op_of_node must have shape ({num_nodes},), "
                         f"got {arr.shape}")
    if arr.size and (int(arr.min()) < 0 or int(arr.max()) >= num_ops):
        bad = arr[(arr < 0) | (arr >= num_ops)]
        raise ValueError(
            f"op_of_node out of range for {num_ops} operators: "
            f"{bad.reshape(-1)[:8].tolist()} (valid range "
            f"[0, {num_ops - 1}])")


def wavefront_eval(wplan: WavefrontPlan, x: jax.Array,
                   op_of_node: jax.Array, weights: jax.Array, *,
                   bias: Optional[jax.Array] = None,
                   activation="relu",
                   bm: int = 8, bn: int = 128, bk: int = 512,
                   segmm_schedule: Optional[str] = None,
                   segmm_path: Optional[str] = None,
                   return_levels: bool = False):
    """Evaluate every node of the DAG in dependency order, level by level.

    Per node ``v`` with operator ``o = op_of_node[v]``::

        h[v] = act((x[v] + sum of h[u] over dependency edges u -> v)
                   @ weights[o] + bias[o])

    ``x``: ``[V, K]`` per-node inputs; ``weights``: ``[O, K, K]`` (square:
    the recursion feeds node outputs back through the same combine, so
    output width must equal input width); ``bias``: optional ``[O, K]``;
    ``activation``: a name from :data:`ACTIVATIONS` or any jnp callable.
    Returns ``[V, K]`` f32 (with the level count actually run when
    ``return_levels=True`` — equal to ``wplan.num_levels`` by the
    build-time validation).

    The loop body runs the three balanced pieces described in the module
    docstring; the dependency combine rides ``wplan.plan``'s (schedule,
    path) and the level GEMM maps the same plan onto the segmm policies
    via :func:`~repro.kernels.segmm.ops.plan_policy` (override with
    ``segmm_schedule``/``segmm_path``).  Every per-node result is
    committed at exactly one level, after all its predecessors — with
    exactly-summable data (integer-valued f32, exact activations) the
    result is **bitwise identical** across all six schedules and both
    execution paths, and to the sequential per-node NumPy oracle
    (``tests/_conformance.py::np_wavefront``).
    """
    plan = wplan.plan
    V = plan.num_vertices
    x = jnp.asarray(x, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    if x.ndim != 2 or x.shape[0] != V:
        raise ValueError(f"x must be [num_nodes={V}, K], got {x.shape}")
    if weights.ndim != 3 or weights.shape[1] != weights.shape[2]:
        raise ValueError(
            f"weights must be [num_ops, K, K] (square per-op matrices: "
            f"node outputs feed back through the combine), got "
            f"{weights.shape}")
    K = x.shape[1]
    num_ops = weights.shape[0]
    if weights.shape[1] != K:
        raise ValueError(f"weights feature width {weights.shape[1]} != "
                         f"input width {K}")
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)
        if bias.shape != (num_ops, K):
            raise ValueError(f"bias must be [num_ops={num_ops}, K={K}], "
                             f"got {bias.shape}")
    _validate_ops(op_of_node, num_ops, V)
    op_of_node = jnp.asarray(op_of_node, jnp.int32)
    act = _resolve_activation(activation)
    if V == 0:
        h = jnp.zeros((0, K), jnp.float32)
        return (h, jnp.int32(0)) if return_levels else h

    src = plan.src
    unit = lambda e: jnp.ones(e.shape, jnp.float32)

    def combine(h, resolved):
        # one balanced advance per feature column: [V, K] -> [K, V] -> back
        col_adv = lambda col: advance(plan, resolved,
                                      lambda e: col[src[e]], combiner="sum")
        return jax.vmap(col_adv)(h.T).T

    def body(state):
        level, h, indeg, resolved = state
        ready = jnp.logical_and(indeg == 0, jnp.logical_not(resolved))
        combined = x + combine(h, resolved)
        z = level_grouped_matmul(combined, op_of_node, weights,
                                 num_ops=num_ops, plan=plan,
                                 schedule=segmm_schedule, path=segmm_path,
                                 bm=bm, bn=bn, bk=bk)
        if bias is not None:
            z = z + bias[op_of_node]
        # each output row depends only on its own combined row, so the
        # masked commit keeps non-ready rows' (discarded) work from ever
        # touching the result — the bitwise-stability argument
        h = jnp.where(ready[:, None], act(z), h)
        resolved = jnp.logical_or(resolved, ready)
        # the generalized bucket loop: decrement each successor's counter
        # once per resolved in-edge (unit-valued advance over the edges
        # leaving this level)
        dec = advance(plan, ready, unit, combiner="sum")
        indeg = indeg - dec.astype(jnp.int32)
        return level + 1, h, indeg, resolved

    def cond(state):
        level, _, _, resolved = state
        # the level bound is host-validated (acyclic => exactly
        # num_levels iterations); the all-resolved check mirrors the
        # graph drivers' empty-frontier termination
        return jnp.logical_and(level < wplan.num_levels,
                               jnp.logical_not(jnp.all(resolved)))

    state0 = (jnp.int32(0), jnp.zeros((V, K), jnp.float32),
              wplan.in_degrees(), jnp.zeros((V,), bool))
    levels_run, h, _, _ = jax.lax.while_loop(cond, body, state0)
    return (h, levels_run) if return_levels else h


# ---------------------------------------------------------------------------
# Ragged-forest batching (data/packing.py applied to trees).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackedForest:
    """A ragged forest packed into one block-diagonal dependency DAG.

    ``dag`` unions every tree (node ids offset by ``node_offsets``); its
    wavefront levels advance all trees simultaneously — level ``l`` holds
    level-``l`` nodes of *every* tree, which is what turns a forest of
    ragged recursions into one segmented matmul per level.  ``row_*`` are
    the balanced batch-row boundaries from
    :func:`repro.data.packing.pack_documents` (atoms = nodes, tiles =
    trees, processors = rows): row ``r`` owns nodes
    ``[row_node_starts[r], row_node_starts[r+1])`` of the concatenated
    node stream.
    """

    dag: Graph
    node_offsets: np.ndarray    # [T+1] node id base of each tree
    row_node_starts: jax.Array  # [R+1] balanced node split across rows
    row_tree_starts: jax.Array  # [R+1] tree split across rows
    num_rows: int

    @property
    def num_trees(self) -> int:
        return len(self.node_offsets) - 1

    def tree_slice(self, t: int) -> slice:
        """Node-id range of tree ``t`` inside the packed DAG."""
        return slice(int(self.node_offsets[t]), int(self.node_offsets[t + 1]))


def pack_forest(trees: Sequence[Union[Graph, CSR]],
                num_rows: Optional[int] = None) -> PackedForest:
    """Batch a ragged forest of dependency DAGs into one padded DAG.

    Node counts vary wildly across trees — the load-balancing problem
    :mod:`repro.data.packing` already solves for documents — so the row
    split reuses :func:`~repro.data.packing.pack_documents` verbatim
    (which also supplies the guards: an empty forest or a zero-node tree
    raises a clean :class:`ValueError` there instead of silently
    mis-packing; single-node trees are legal and common).  The returned
    block-diagonal union is an ordinary :class:`~repro.sparse.graph.Graph`
    — feed it straight to :func:`build_wavefront`.
    """
    from repro.data.packing import pack_documents
    trees = list(trees)
    if not trees:
        raise ValueError("pack_forest needs at least one tree "
                         "(got an empty forest)")
    csrs = [t.csr if isinstance(t, Graph) else t for t in trees]
    counts = np.asarray([c.shape[0] for c in csrs], np.int64)
    if num_rows is None:
        num_rows = min(len(trees), 8)
    # the packing guards vet counts/num_rows (zero-node trees, bad rows)
    node_starts, tree_starts = pack_documents(
        jnp.asarray(counts, jnp.int32), num_rows)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    total_nodes = int(offsets[-1])
    row_offsets = [np.zeros(1, np.int64)]
    cols, vals = [], []
    edge_base = 0
    for t, c in enumerate(csrs):
        ro = np.asarray(c.row_offsets, np.int64)
        row_offsets.append(ro[1:] + edge_base)
        cols.append(np.asarray(c.col_indices, np.int64) + offsets[t])
        vals.append(np.asarray(c.values, np.float32))
        edge_base += int(ro[-1])
    dag = Graph(CSR(jnp.asarray(np.concatenate(row_offsets), jnp.int32),
                    jnp.asarray(np.concatenate(cols), jnp.int32),
                    jnp.asarray(np.concatenate(vals), jnp.float32),
                    (total_nodes, total_nodes), edge_base))
    return PackedForest(dag=dag, node_offsets=offsets.astype(np.int64),
                        row_node_starts=node_starts,
                        row_tree_starts=tree_starts, num_rows=int(num_rows))
