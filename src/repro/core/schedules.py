"""Load-balancing schedules (paper §3.2, §4.2, §5.2).

A *schedule* partitions the atoms/tiles of a :class:`~repro.core.work.WorkSpec`
across ``num_blocks`` processors.  On the GPU the paper's processors are
threads/warps/blocks/cooperative-groups; on TPU they are Pallas grid blocks
(and, one level up, chips of the device mesh — the same partitioners drive
cross-chip balancing of MoE dispatch and document packing).

All partitioners are pure, vectorized JAX: O(G log T) ``searchsorted`` calls
computed *before* the kernel launch.  This replaces the GPU's per-thread
in-kernel binary search — on TPU the partition is static per input, so we lift
the search out of the kernel and feed block coordinates in via scalar prefetch.

Every partitioner returns a :class:`Partition` with the same contract, so work
execution (kernels, executors) is schedule-agnostic — the separation of
concerns at the heart of the paper.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.work import WorkSpec


class Schedule(str, enum.Enum):
    """Named schedules shipped with the library (paper §5.2)."""

    THREAD_MAPPED = "thread_mapped"    # tile-per-lane (paper Listing 2)
    GROUP_MAPPED = "group_mapped"      # tiles-per-group + prefix-sum binning
    WARP_MAPPED = "warp_mapped"        # group_mapped with group = 128 lanes
    BLOCK_MAPPED = "block_mapped"      # group_mapped with group = 8*128 lanes
    NONZERO_SPLIT = "nonzero_split"    # equal atoms per block + fixup
    MERGE_PATH = "merge_path"          # equal (atoms + tiles) per block
    # dynamic schedules (repro.core.dynamic; Atos-style work queues)
    CHUNKED = "chunked"                # oversplit into K*B chunks + queue
    ADAPTIVE = "adaptive"              # inspect-then-balance two-phase
    # sentinel: cost-model-driven selection (repro.core.autotune)
    AUTO = "auto"

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return self.value


#: Schedules that produce partitions directly (everything except AUTO).
CONCRETE_SCHEDULES = (
    Schedule.THREAD_MAPPED, Schedule.GROUP_MAPPED, Schedule.WARP_MAPPED,
    Schedule.BLOCK_MAPPED, Schedule.NONZERO_SPLIT, Schedule.MERGE_PATH,
    Schedule.CHUNKED, Schedule.ADAPTIVE,
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Partition:
    """Assignment of atom/tile subsequences to ``num_blocks`` processors.

    Block ``b`` owns atoms ``[atom_starts[b], atom_starts[b+1])`` and touches
    tiles ``[tile_starts[b], tile_starts[b+1]]`` — the final tile may be
    *shared* with block ``b+1`` (a partial tile), in which case the executor
    must combine cross-block partial results (the merge-path "fixup").
    For tile-aligned schedules (thread/group-mapped) tiles are never shared.
    """

    schedule: Schedule                 # static
    num_blocks: int                    # static
    items_per_block: int               # static: balance granule per block
    atom_starts: jax.Array             # int32 [num_blocks + 1]
    tile_starts: jax.Array             # int32 [num_blocks + 1]
    tile_aligned: bool                 # static: atom_starts on tile boundaries
    # Dynamic (chunked) schedules oversplit the work into num_blocks entries
    # ("chunks") that a smaller pool of physical processors drains as a
    # queue: ``block_map[c]`` is the physical block assigned chunk ``c`` and
    # ``num_physical_blocks`` the pool size.  None for static schedules,
    # where entries and physical blocks coincide.
    block_map: Optional[jax.Array] = None       # int32 [num_blocks] or None
    num_physical_blocks: Optional[int] = None   # static
    # Static sizing hints captured at (concrete) build time.  Executors need
    # static window shapes; under jit the boundary arrays are tracers, so
    # without these hints they must fall back to worst-case windows — or,
    # worse, guess from items_per_block, which undercounts the tile span of
    # blocks crossing empty tiles.  atom_span = max atoms any block owns;
    # tile_span = max tiles any block touches (inclusive of a shared tile).
    atom_span: Optional[int] = None             # static
    tile_span: Optional[int] = None             # static
    # Inverted, padded CSR-style view of ``block_map``, built once at
    # construction (see :func:`invert_block_map`): ``block_chunks[p, i]`` is
    # the i-th chunk physical block ``p`` pops from its queue (rows padded
    # with 0 past ``block_chunk_counts[p]``).  This is the scalar-prefetch
    # payload of the native chunk-walking Pallas kernels — each block reads
    # its row and loops over its chunks *inside* the kernel.  None when
    # ``block_map`` is None (static schedules: block == chunk) or traced.
    block_chunks: Optional[jax.Array] = None        # int32 [P, max_chunks]
    block_chunk_counts: Optional[jax.Array] = None  # int32 [P]

    def tree_flatten(self):
        return ((self.atom_starts, self.tile_starts, self.block_map,
                 self.block_chunks, self.block_chunk_counts),
                (self.schedule, self.num_blocks, self.items_per_block,
                 self.tile_aligned, self.num_physical_blocks,
                 self.atom_span, self.tile_span))

    @classmethod
    def tree_unflatten(cls, aux, children):
        (atom_starts, tile_starts, block_map,
         block_chunks, block_chunk_counts) = children
        (schedule, num_blocks, items_per_block, tile_aligned,
         num_physical_blocks, atom_span, tile_span) = aux
        return cls(schedule=schedule, num_blocks=num_blocks,
                   items_per_block=items_per_block, atom_starts=atom_starts,
                   tile_starts=tile_starts, tile_aligned=tile_aligned,
                   block_map=block_map,
                   num_physical_blocks=num_physical_blocks,
                   atom_span=atom_span, tile_span=tile_span,
                   block_chunks=block_chunks,
                   block_chunk_counts=block_chunk_counts)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def invert_block_map(block_map: jax.Array, num_physical_blocks: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Invert a chunk -> block map into per-block chunk lists (padded CSR).

    Returns ``(block_chunks, block_chunk_counts)``: ``block_chunks[p, :]``
    lists the chunks assigned to physical block ``p`` in chunk order (the
    pop order of its queue), padded with ``0`` up to the max queue length;
    ``block_chunk_counts[p]`` is the true length.  This is the static-shape
    payload the Pallas chunk-walking kernels scalar-prefetch: TPU grids
    cannot pop a shared queue at runtime, so the queue discipline is
    materialized per block before launch.

    Requires a concrete (non-traced) ``block_map`` — inversion is an
    inspector step.
    """
    if isinstance(block_map, jax.core.Tracer):
        raise ValueError("invert_block_map needs a concrete block_map "
                         "(schedule inversion is a pre-launch inspector)")
    bm = np.asarray(block_map, np.int64)
    num_physical_blocks = max(int(num_physical_blocks), 1)
    counts = np.bincount(bm, minlength=num_physical_blocks)
    max_chunks = max(int(counts.max()) if counts.size else 0, 1)
    chunks = np.zeros((num_physical_blocks, max_chunks), np.int32)
    # stable sort groups chunks by block while preserving chunk order
    # within each block — i.e. the queue's pop order
    order = np.argsort(bm, kind="stable")
    slot = np.arange(bm.size) - np.concatenate(
        [[0], np.cumsum(counts)])[bm[order]]
    chunks[bm[order], slot] = order
    return (jnp.asarray(chunks),
            jnp.asarray(counts.astype(np.int32)))


def finalize_partition(part: Partition) -> Partition:
    """Record static atom/tile span hints while boundaries are concrete.

    Partitions are built by a pre-launch inspector, so boundaries are
    normally concrete here even when the *consumer* later runs under jit
    (where they become closure tracers and can no longer be concretised).
    Also builds the inverted ``block_chunks`` view of ``block_map`` (once,
    here) so the native chunk-walking kernels can scalar-prefetch it.
    No-op for traced boundaries.
    """
    if (part.atom_span is not None or part.num_blocks < 1
            or isinstance(part.atom_starts, jax.core.Tracer)):
        return part
    atom_span = int(jnp.max(part.atom_starts[1:] - part.atom_starts[:-1]))
    tile_span = int(jnp.max(part.tile_starts[1:] - part.tile_starts[:-1])) + 1
    block_chunks, block_chunk_counts = part.block_chunks, part.block_chunk_counts
    if (part.block_map is not None and block_chunks is None
            and not isinstance(part.block_map, jax.core.Tracer)):
        block_chunks, block_chunk_counts = invert_block_map(
            part.block_map, part.num_physical_blocks or part.num_blocks)
    return dataclasses.replace(part, atom_span=max(atom_span, 1),
                               tile_span=max(tile_span, 1),
                               block_chunks=block_chunks,
                               block_chunk_counts=block_chunk_counts)


# ---------------------------------------------------------------------------
# Tile-aligned schedules: thread-, warp-, block- and group-mapped.
# ---------------------------------------------------------------------------

def tile_mapped_partition(spec: WorkSpec, num_blocks: int,
                          schedule: Schedule = Schedule.THREAD_MAPPED
                          ) -> Partition:
    """Assign an equal, contiguous span of *tiles* to each block.

    This is the common partition underlying the paper's thread-, warp-,
    block- and group-mapped schedules: equal tile counts, arbitrary atom
    counts (so imbalanced when tile sizes vary).  On the GPU the paper
    strides tiles by grid size; on TPU contiguous spans are preferred so a
    block's atoms form one dense VMEM window.
    """
    tiles_per_block = _ceil_div(spec.num_tiles, num_blocks)
    tile_starts = jnp.minimum(
        jnp.arange(num_blocks + 1, dtype=jnp.int32) * tiles_per_block,
        spec.num_tiles)
    atom_starts = spec.tile_offsets[tile_starts]
    return finalize_partition(Partition(
        schedule=schedule, num_blocks=num_blocks,
        items_per_block=tiles_per_block,
        atom_starts=atom_starts.astype(jnp.int32),
        tile_starts=tile_starts, tile_aligned=True))


def group_mapped_partition(spec: WorkSpec, num_blocks: int,
                           group_tiles: Optional[int] = None) -> Partition:
    """Paper §5.2.3 — the novel Cooperative-Groups generalization.

    A "group" owns ``group_tiles`` tiles; within the group, a prefix sum of
    atoms-per-tile (in VMEM scratch on TPU, shared memory on GPU) maps lanes
    to atoms and ``get_tile(atom)`` is a binary search into that prefix sum.
    The partition itself is tile-aligned; the *execution strategy* (atom-
    parallel within the group) is what distinguishes it — see
    :mod:`repro.core.execute` and the Pallas kernels.
    """
    if group_tiles is not None:
        num_blocks = _ceil_div(spec.num_tiles, group_tiles)
    return tile_mapped_partition(spec, num_blocks, Schedule.GROUP_MAPPED)


# ---------------------------------------------------------------------------
# Atom-aligned schedule: nonzero splitting.
# ---------------------------------------------------------------------------

def nonzero_split_partition(spec: WorkSpec, num_blocks: int) -> Partition:
    """Equal *atoms* per block (Baxter's / Dalton's nonzero split).

    Perfectly balanced in atoms but ignores per-tile bookkeeping cost; blocks
    may start/end mid-tile, requiring a fixup pass.  Tile coordinates are
    recovered with one vectorized searchsorted over the block boundaries.
    """
    atoms_per_block = _ceil_div(max(spec.num_atoms, 1), num_blocks)
    atom_starts = jnp.minimum(
        jnp.arange(num_blocks + 1, dtype=jnp.int32) * atoms_per_block,
        spec.num_atoms)
    # tile_starts[b] = tile owning the first atom of block b.
    tile_starts = (jnp.searchsorted(spec.tile_offsets, atom_starts,
                                    side="right").astype(jnp.int32) - 1)
    tile_starts = jnp.clip(tile_starts, 0, spec.num_tiles)
    return finalize_partition(Partition(
        schedule=Schedule.NONZERO_SPLIT, num_blocks=num_blocks,
        items_per_block=atoms_per_block,
        atom_starts=atom_starts, tile_starts=tile_starts,
        tile_aligned=False))


# ---------------------------------------------------------------------------
# Merge-path (paper §5.2.1; Merrill & Garland / Green et al.).
# ---------------------------------------------------------------------------

def merge_path_partition(spec: WorkSpec, num_blocks: int) -> Partition:
    """Split ``num_atoms + num_tiles`` work items exactly evenly.

    Model: a 2-D merge of ``A[t] = tile_offsets[t+1]`` (tile-end markers,
    consumed *after* the tile's atoms) against ``B = 0..num_atoms-1`` (atom
    indices).  Block ``b`` starts at diagonal ``d_b = b * items_per_block``.
    The split point of diagonal ``d`` is the largest ``t`` such that
    ``tile_offsets[t] + t <= d`` (both row-end count and atom count consumed
    before the path crosses the diagonal); the atom coordinate is then
    ``d - t``.  ``f(t) = tile_offsets[t] + t`` is *strictly* increasing, so a
    single vectorized ``searchsorted`` over all block boundaries replaces the
    per-thread binary search of the CUDA implementation.
    """
    total = spec.total_work()
    items_per_block = _ceil_div(max(total, 1), num_blocks)
    diagonals = jnp.minimum(
        jnp.arange(num_blocks + 1, dtype=jnp.int32) * items_per_block, total)
    path = spec.tile_offsets.astype(jnp.int32) + jnp.arange(
        spec.num_tiles + 1, dtype=jnp.int32)  # f(t), strictly increasing
    tile_starts = (jnp.searchsorted(path, diagonals, side="right")
                   .astype(jnp.int32) - 1)
    tile_starts = jnp.clip(tile_starts, 0, spec.num_tiles)
    atom_starts = diagonals - tile_starts
    return finalize_partition(Partition(
        schedule=Schedule.MERGE_PATH, num_blocks=num_blocks,
        items_per_block=items_per_block,
        atom_starts=atom_starts.astype(jnp.int32),
        tile_starts=tile_starts, tile_aligned=False))


# ---------------------------------------------------------------------------
# Registry / dispatch.
# ---------------------------------------------------------------------------

def partition_build_count() -> int:
    """Process-wide count of concrete partition builds via make_partition.

    Monotonic.  Counts every concrete-schedule build, including the ones
    the cost models perform while *scoring*: ``schedule="auto"`` on a cold
    autotune cache therefore adds one count per scored schedule plus one
    for the winning build (a warm cache adds exactly one).  Regression
    tests should pin explicit schedules, where one call == one build.
    Ops that batch many computations over one workload (spmm over B's
    columns, graph traversals over iterations) must build their Partition
    once, not per column/iteration; counting at the registry keeps that
    checkable from the outside.
    """
    return telemetry.counters().get("partition_builds", 0)


def make_partition(spec: WorkSpec, schedule: Schedule | str,
                   num_blocks: int, *, chunk_policy: str = "lpt"
                   ) -> Partition:
    schedule = Schedule(schedule)
    if schedule != Schedule.AUTO:
        telemetry.count("partition_builds")
    if schedule in (Schedule.THREAD_MAPPED,):
        return tile_mapped_partition(spec, num_blocks, schedule)
    if schedule in (Schedule.GROUP_MAPPED, Schedule.WARP_MAPPED,
                    Schedule.BLOCK_MAPPED):
        part = group_mapped_partition(spec, num_blocks)
        return dataclasses.replace(part, schedule=schedule)
    if schedule == Schedule.NONZERO_SPLIT:
        return nonzero_split_partition(spec, num_blocks)
    if schedule == Schedule.MERGE_PATH:
        return merge_path_partition(spec, num_blocks)
    if schedule == Schedule.CHUNKED:
        from repro.core.dynamic import chunked_partition
        return chunked_partition(spec, num_blocks, policy=chunk_policy)
    if schedule == Schedule.ADAPTIVE:
        from repro.core.dynamic import adaptive_partition
        return adaptive_partition(spec, num_blocks)
    if schedule == Schedule.AUTO:
        from repro.core.autotune import select_schedule
        return make_partition(spec, select_schedule(spec, num_blocks),
                              num_blocks)
    raise ValueError(f"unknown schedule: {schedule}")
