"""Work execution (paper §3.3 / §4.3) — schedule-agnostic consumers.

The paper's users write ``for tile in cfg.tiles(): for atom in cfg.atoms(tile)``
inside their own CUDA kernel.  The TPU analogue: the user supplies an
*atom transform* (a function of atom index -> value, e.g.
``lambda nz: vals[nz] * x[col[nz]]`` for SpMV) and a reduction; the executor
consumes a :class:`Partition` and materializes the blocked execution.

Three executors are provided, behind one dispatcher:

* :func:`tile_reduce` — the oracle path: one segment-sum over the whole
  problem.  Schedule-independent result, used as ground truth everywhere.
* :func:`blocked_tile_reduce` — the *faithful blocked* execution: every block
  processes exactly its partition slice with static shapes + masking, interior
  tiles complete locally, and boundary tiles are combined in a fixup pass.
  This is bit-for-bit the algorithm the Pallas kernels implement, kept in
  pure JAX so kernels have an executable specification to test against.
* :func:`native_chunk_tile_reduce` — the *device-side* execution: a Pallas
  kernel (``repro.kernels.spmv_merge.chunk_walk_reduce``) whose grid walks
  each *physical* block's chunk queue (the inverted ``Partition.block_map``,
  scalar-prefetched) one popped chunk per step — the Atos work-queue
  discipline on-device, which is where the paper's dynamic schedules
  actually pay off.

:func:`execute_tile_reduce` routes any Partition (static, chunked, adaptive)
to one of the latter two via :class:`ExecutionPath`; ``"auto"`` picks the
native kernel whenever the partition carries the structures it needs.
Every Pallas kernel of the repo launches through :func:`pallas_call`,
which interprets it where the program is lowered for the CPU.
"""
from __future__ import annotations

import enum
from typing import Callable, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.schedules import Partition, invert_block_map
from repro.core.segops import segment_sum
from repro.core.work import WorkSpec

#: The atom transform: ``[n]`` int32 atom ids -> ``[n]`` values, or the
#: ``[num_atoms]`` values themselves.  :func:`atom_values` turns either into
#: the values, once per call, and the executors read only those.  Callers
#: that already hold per-atom values (the traversal drivers' ``dist[src] +
#: w``) pass them as they are: wrapped in ``lambda e: values[e]``, each
#: advance would pay one more gather over every atom (0.24 s at Graph500
#: scale 20 on one TPU v5e).
AtomFn = Union[Callable[[jax.Array], jax.Array], jax.Array]

#: Atoms per f32 ``(8, 128)`` VMEM tile.  The native kernel walks a chunk in
#: whole tiles, so every value window starts at a multiple of this
#: (:func:`window_slots`), on both execution paths.
WINDOW_ALIGN = 8 * 128


def pallas_call(kernel, *, name: str, **kwargs):
    """``pl.pallas_call`` that interprets exactly where it is lowered for
    the CPU.

    The one place that chooses between the Pallas interpreter and a
    compiled Mosaic kernel; every kernel of the repo is launched through
    it.  The choice is made per lowering platform
    (``lax.platform_dependent``), not per process: the same traced program
    runs in the interpreter under the CPU tests and compiles natively when
    lowered for a TPU, attached or described by a topology.  Every launch
    runs in the ``kernel`` scope, under the kernel's stable ``name``.
    """
    from jax.experimental import pallas as pl

    def call(*args):
        with jax.named_scope("kernel"):
            return jax.lax.platform_dependent(
                *args,
                cpu=pl.pallas_call(kernel, interpret=True, name=name,
                                   **kwargs),
                default=pl.pallas_call(kernel, name=name, **kwargs))
    return call

#: Reduction combiners usable by every executor.  ``sum`` is the paper's
#: tile-reduce; ``min``/``max`` are the graph advance's scatter-min (SSSP
#: relax) and scatter-or (BFS frontier expansion, over {0, 1} values).  All
#: three are associative and commutative; min/max are additionally *exact*
#: in floating point, so every schedule/path produces identical bits.
COMBINER_IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}


def _check_combiner(combiner: str, dtype) -> float:
    """Validate and return the combiner's identity element."""
    if combiner not in COMBINER_IDENTITY:
        raise ValueError(f"unknown combiner: {combiner!r} "
                         f"(expected one of {sorted(COMBINER_IDENTITY)})")
    if combiner != "sum" and not jnp.issubdtype(jnp.dtype(dtype),
                                                jnp.floating):
        raise ValueError(f"combiner {combiner!r} needs a floating dtype "
                         f"(its identity is +/-inf), got {jnp.dtype(dtype)}")
    return COMBINER_IDENTITY[combiner]


@jax.named_scope("scatter")
def _segment_reduce(combiner: str, values: jax.Array, segment_ids: jax.Array,
                    num_segments: int) -> jax.Array:
    """Segmented reduction under the named combiner (identity fill)."""
    if combiner == "sum":
        return segment_sum(values, segment_ids, num_segments)
    if combiner == "min":
        return jax.ops.segment_min(values, segment_ids,
                                   num_segments=num_segments)
    return jax.ops.segment_max(values, segment_ids,
                               num_segments=num_segments)


class ExecutionPath(str, enum.Enum):
    """Which executor consumes a Partition.

    ``PURE`` — the pure-JAX blocked executor (:func:`blocked_tile_reduce`),
    always available (also the name segmm's permuted-grid fallback routes
    under).  ``NATIVE`` — the Pallas chunk-walking kernel.  ``AUTO`` — native
    when the partition supports it, pure otherwise.
    """

    AUTO = "auto"
    PURE = "pure"
    NATIVE = "native"

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return self.value


def supports_native_execution(part: Partition) -> bool:
    """True when a Partition carries what the chunk-walking kernel needs.

    Requirements: static ``atom_span``/``tile_span`` window hints (the
    kernel's VMEM windows are static shapes) and, for dynamic schedules, a
    concrete inverted ``block_map`` view (or a ``block_map`` that can still
    be inverted).  Partitions built under jit tracing have neither — the
    inspector must run pre-launch for the native path, by design.
    """
    if part.atom_span is None or part.tile_span is None:
        return False
    if part.block_map is None:
        return True                       # static schedule: block == chunk
    if part.block_chunks is not None:
        return True
    return not isinstance(part.block_map, jax.core.Tracer)


def resolve_execution_path(request: ExecutionPath | str, *,
                           native_supported: bool) -> ExecutionPath:
    """Collapse an ``auto``/``pure``/``native`` request to a concrete path."""
    request = ExecutionPath(request)
    if request == ExecutionPath.NATIVE and not native_supported:
        raise ValueError(
            "native execution path requested but the partition/workload "
            "does not support it (needs concrete span hints + block map; "
            "build the partition outside jit)")
    if request == ExecutionPath.AUTO:
        return (ExecutionPath.NATIVE if native_supported
                else ExecutionPath.PURE)
    return request


def choose_execution_path(part: Partition,
                          request: ExecutionPath | str = ExecutionPath.AUTO
                          ) -> ExecutionPath:
    """The dispatcher's routing rule for a given Partition."""
    return resolve_execution_path(request,
                                  native_supported=supports_native_execution(part))


@jax.custom_batching.custom_vmap
def lane_take(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` for a 1-D ``x``, lane-major under ``jax.vmap``.

    Mapped over lanes of ``x`` with shared indices, JAX's own rule gathers
    one ``[lanes]`` column per index, and XLA:TPU lays that result out with
    the lanes minor: ``[E, 8]`` padded to 128 lanes, 16 times the bytes
    (two such gathers took 30 GB in the 8-lane serving step at Graph500
    scale 20).  Here the lanes are flattened into one scalar gather at
    offsets ``lane * len(x)`` instead, so the result is ``[lanes, *idx]``
    with the lanes leading.
    """
    return x[idx]


@lane_take.def_vmap
def _lane_take_vmap(axis_size, in_batched, x, idx):
    x_batched, idx_batched = in_batched
    if idx_batched or not x_batched or axis_size * x.shape[1] >= 2 ** 31:
        if not x_batched:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        if not idx_batched:
            idx = jnp.broadcast_to(idx, (axis_size,) + idx.shape)
        return jax.vmap(lambda a, i: a[i])(x, idx), True
    offsets = (jnp.arange(axis_size, dtype=idx.dtype) * x.shape[1]
               ).reshape((axis_size,) + (1,) * idx.ndim)
    return x.reshape(-1)[offsets + idx], True


def atom_values(atom_fn: AtomFn, num_atoms: int, dtype) -> jax.Array:
    """The atom transform over every atom: ``[num_atoms]`` values."""
    values = (atom_fn(jnp.arange(num_atoms, dtype=jnp.int32))
              if callable(atom_fn) else jnp.asarray(atom_fn))
    return values.astype(dtype)


def tile_reduce(spec: WorkSpec, atom_fn: AtomFn,
                dtype=jnp.float32, *, combiner: str = "sum",
                atom_mask: jax.Array | None = None) -> jax.Array:
    """Oracle: per-tile ``combiner``-reduce of ``atom_fn(atom)`` over atoms.

    ``atom_mask`` (bool ``[num_atoms]``, optional) drops atoms by replacing
    their value with the combiner's identity — the frontier mask of a graph
    advance.  Tiles with no (unmasked) atoms come back as the identity.
    """
    identity = _check_combiner(combiner, dtype)
    values = _masked_values(atom_fn, spec.num_atoms, dtype, identity,
                            atom_mask)
    return _segment_reduce(combiner, values, spec.atom_tile_ids(),
                           spec.num_tiles)


def _window_sizes(spec: WorkSpec, part: Partition) -> Tuple[int, int]:
    """Static (atom window, local tile window) sizes for blocked execution.

    Preferred source: the span hints captured by ``finalize_partition`` when
    the boundaries were still concrete (under jit the closure-captured
    boundary arrays are tracers, so they cannot be concretised here).
    Fallbacks are schedule-aware worst cases.
    """
    from repro.core.schedules import Schedule

    if part.atom_span is not None:
        window = max(part.atom_span, 1)
    elif part.tile_aligned:
        # items_per_block counts *tiles*; the atom window is data-dependent.
        try:
            window = max(int(jnp.max(part.atom_starts[1:]
                                     - part.atom_starts[:-1])), 1)
        except jax.errors.ConcretizationTypeError:
            window = max(spec.num_atoms, 1)
    else:
        # merge-path / chunked / nonzero-split: items_per_block bounds atoms.
        window = max(int(part.items_per_block), 1)

    # Local tile window: a block touches tiles [tile_starts[b],
    # tile_starts[b+1]] inclusive.  Sizing it from items_per_block alone
    # undercounts when a block's span crosses *empty* tiles (atoms bound
    # work, not tile span) and would silently drop their neighbours' sums.
    if part.tile_span is not None:
        local_tiles = max(part.tile_span, 1)
    else:
        try:
            local_tiles = max(int(jnp.max(part.tile_starts[1:]
                                          - part.tile_starts[:-1])) + 1, 1)
        except jax.errors.ConcretizationTypeError:
            if part.schedule == Schedule.MERGE_PATH:
                # merge items bound atoms + tile markers: span <= items + 1
                local_tiles = max(int(part.items_per_block), 1) + 1
            elif part.tile_aligned and part.schedule not in (
                    Schedule.CHUNKED, Schedule.ADAPTIVE):
                local_tiles = max(int(part.items_per_block), 1) + 1
            else:
                # no static bound relates atoms to tile span: worst case
                local_tiles = spec.num_tiles + 1
    return window, local_tiles


@jax.named_scope("fixup")
def fixup_partials(spec: WorkSpec, part: Partition, partials: jax.Array,
                   local_tiles: int, combiner: str = "sum") -> jax.Array:
    """Scatter-combine per-chunk partials at their global tile offsets.

    Merrill & Garland's "segmented fixup", adapted: TPU grid blocks cannot
    order-depend, so the fixup is a separate reduction over per-block
    partials.  Shared by the pure-JAX and native Pallas paths so the two are
    reduction-order-identical.  Local-tile bins a block never touched carry
    the combiner's identity, so they drop out of the scatter.
    """
    gtid = part.tile_starts[:-1, None] + jnp.arange(local_tiles,
                                                    dtype=jnp.int32)[None, :]
    gtid = jnp.where(gtid < spec.num_tiles, gtid, spec.num_tiles)  # drop OOB
    return _segment_reduce(combiner, partials.reshape(-1), gtid.reshape(-1),
                           spec.num_tiles + 1)[:-1]


def blocked_tile_reduce(spec: WorkSpec, part: Partition, atom_fn: AtomFn,
                        dtype=jnp.float32, *, combiner: str = "sum",
                        atom_mask: jax.Array | None = None) -> jax.Array:
    """Blocked execution faithful to the partition (pure JAX).

    Shapes are static: each block materializes a ``[items_per_block]`` window
    of atoms (masked past its end) and reduces into at most
    ``items_per_block + 1`` local tiles via a one-hot contraction — the same
    MXU-shaped inner loop as the Pallas kernels.  Cross-block partial tiles
    are resolved by the shared scatter fixup.

    ``combiner`` selects the reduction (``sum``/``min``/``max``);
    ``atom_mask`` (bool ``[num_atoms]``) is the frontier mask of a graph
    advance — masked atoms contribute the combiner's identity, exactly as if
    they were past the block's end.
    """
    identity = _check_combiner(combiner, dtype)
    if spec.num_atoms == 0:
        return jnp.full((spec.num_tiles,), identity, dtype)
    grid = part.num_blocks
    window, local_tiles = _window_sizes(spec, part)

    with jax.named_scope("windows"):
        atom_base = part.atom_starts[:-1]                   # [G]
        idx = (atom_base[:, None]
               + jnp.arange(window, dtype=jnp.int32)[None, :])
        valid = idx < part.atom_starts[1:, None]            # [G, W]
        safe_idx = jnp.clip(idx, 0, max(spec.num_atoms - 1, 0))
        if atom_mask is not None:
            valid = jnp.logical_and(valid, atom_mask[safe_idx])

        values = lane_take(atom_values(atom_fn, spec.num_atoms, dtype),
                           safe_idx)
        values = jnp.where(valid, values, jnp.asarray(identity, dtype))

        tile_ids = spec.atom_tile_ids()                      # [A]
        tids = tile_ids[safe_idx]                            # [G, W]
        local = tids - part.tile_starts[:-1, None]           # [G, W]
        local = jnp.where(valid, local, local_tiles)         # mask -> OOB bin

        onehot = (local[..., None]
                  == jnp.arange(local_tiles, dtype=jnp.int32)[None, None, :])
        if combiner == "sum":
            # One-hot contraction per block: [G, W] x [W, local_tiles] (MXU).
            partials = jnp.einsum("gw,gwl->gl", values, onehot.astype(dtype))
        else:
            # min/max: masked elementwise reduce over the window — no dot
            # product expresses these, but the window/bin shapes are
            # identical to the sum path so the fixup stays shared.
            contrib = jnp.where(onehot, values[..., None],
                                jnp.asarray(identity, dtype))  # [G, W, L]
            partials = (contrib.min(axis=1) if combiner == "min"
                        else contrib.max(axis=1))

    return fixup_partials(spec, part, partials, local_tiles, combiner)


def _chunk_queue_view(part: Partition) -> Tuple[jax.Array, jax.Array, int]:
    """(block_chunks [P, Cmax], counts [P], P) — identity for static parts."""
    if part.block_chunks is not None:
        counts = part.block_chunk_counts
        return part.block_chunks, counts, int(counts.shape[0])
    if part.block_map is not None:
        phys = part.num_physical_blocks or part.num_blocks
        chunks, counts = invert_block_map(part.block_map, phys)
        return chunks, counts, int(counts.shape[0])
    # static schedule: every block is its own single-chunk queue
    return _single_chunk_queues(part.num_blocks) + (part.num_blocks,)


def _single_chunk_queues(num_chunks: int) -> Tuple[jax.Array, jax.Array]:
    """(block_chunks, counts) with chunk ``c`` alone in queue ``c``."""
    return (jnp.arange(num_chunks, dtype=jnp.int32)[:, None],
            jnp.ones((num_chunks,), jnp.int32))


def native_chunk_tile_reduce(spec: WorkSpec, part: Partition, atom_fn: AtomFn,
                             dtype=jnp.float32, *, combiner: str = "sum",
                             atom_mask: jax.Array | None = None) -> jax.Array:
    """Device-side execution: the Pallas chunk-walking kernel.

    Materializes the atom transform once (``atom_fn`` over all atoms, the
    frontier mask applied as the combiner's identity, plus the ``atom ->
    tile`` map), then launches one grid step per popped chunk of each
    *physical* block's scalar-prefetched queue (see
    ``repro.kernels.spmv_merge.kernel.chunk_walk_reduce``); the shared
    fixup resolves cross-chunk partial tiles.  Same chunk boundaries, local
    bins and fixup as :func:`blocked_tile_reduce`, so min/max results — and
    sums of exactly summable values — are bit-identical to it, as tests
    assert across every schedule and combiner.
    """
    identity = _check_combiner(combiner, dtype)
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        raise ValueError("native path accumulates in float32")
    if spec.num_atoms == 0:
        return jnp.full((spec.num_tiles,), identity, dtype)
    if not supports_native_execution(part):
        raise ValueError("partition does not support the native path "
                         "(see supports_native_execution)")
    from repro.kernels.spmv_merge.kernel import chunk_walk_reduce

    window, local_tiles = _window_sizes(spec, part)
    block_chunks, counts, _ = _chunk_queue_view(part)
    with jax.named_scope("windows"):
        values = _masked_values(atom_fn, spec.num_atoms, dtype, identity,
                                atom_mask)
        partials = chunk_walk_reduce(
            values, spec.atom_tile_ids(), part.atom_starts.astype(jnp.int32),
            part.tile_starts.astype(jnp.int32),
            block_chunks.reshape(-1).astype(jnp.int32),
            counts.astype(jnp.int32), window=window, local_tiles=local_tiles,
            max_chunks=int(block_chunks.shape[1]), combiner=combiner)
    return fixup_partials(spec, part, partials, local_tiles, combiner)


def _masked_values(atom_fn: AtomFn, num_atoms: int, dtype, identity: float,
                   atom_mask: jax.Array | None) -> jax.Array:
    """``atom_fn`` over every atom, masked atoms replaced by ``identity``."""
    values = atom_values(atom_fn, num_atoms, dtype)
    if atom_mask is None:
        return values
    return jnp.where(atom_mask, values, jnp.asarray(identity, dtype))


# ---------------------------------------------------------------------------
# Scatter-reduce: balanced value windows combined by arbitrary per-atom
# output ids (the push-direction graph advance).
# ---------------------------------------------------------------------------

def window_slots(window: int) -> int:
    """Slots per value-window row for chunks of at most ``window`` atoms.

    A row covers whole :data:`WINDOW_ALIGN` tiles from the one holding its
    chunk's first atom (:func:`_window_slot_view`), so up to
    ``WINDOW_ALIGN - 1`` slots precede that atom.
    """
    return -(-(max(window, 1) + WINDOW_ALIGN - 1) // WINDOW_ALIGN) \
        * WINDOW_ALIGN


def _window_slot_view(starts: jax.Array, slots: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Slot -> position addressing of value windows over chunk ``starts``.

    Row ``c`` holds positions ``origin[c] + [0, slots)`` with ``origin[c] =
    starts[c]`` rounded down to a multiple of :data:`WINDOW_ALIGN`.
    Returns ``(pos, in_chunk)``, both ``[C, slots]``; ``in_chunk`` marks the
    slots whose position belongs to the row's own chunk, so every position
    is in exactly one row.  Both execution paths produce windows in this
    layout and both scatters read them through it: it is the whole
    correctness coupling of the window modes, so it lives in one place.
    """
    starts = starts.astype(jnp.int32)
    origin = (starts[:-1] // WINDOW_ALIGN) * WINDOW_ALIGN
    pos = origin[:, None] + jnp.arange(slots, dtype=jnp.int32)[None, :]
    in_chunk = jnp.logical_and(pos >= starts[:-1, None],
                               pos < starts[1:, None])
    return pos, in_chunk


@jax.named_scope("windows")
def blocked_value_windows(spec: WorkSpec, part: Partition, atom_fn: AtomFn,
                          dtype=jnp.float32, *, combiner: str = "sum",
                          atom_mask: jax.Array | None = None) -> jax.Array:
    """Per-block masked value windows ``[num_blocks, slots]`` (pure JAX).

    The first half of a scatter-reduce: each block materializes its
    partition slice of atoms in the shared window layout
    (:func:`_window_slot_view`), applies the atom transform, and replaces
    slots outside the block — or dropped by ``atom_mask`` — with the
    combiner's identity.  These are the push advance's *frontier-compacted
    per-source partials*: windows follow the (source-tile-grouped) atom
    order of the push view, masked to frontier sources; no local binning
    happens because the output ids (edge destinations) are unrelated to the
    walked tiles.
    """
    identity = _check_combiner(combiner, dtype)
    grid = part.num_blocks
    slots = window_slots(_window_sizes(spec, part)[0])
    if spec.num_atoms == 0:
        return jnp.full((grid, slots), identity, dtype)
    pos, valid = _window_slot_view(part.atom_starts, slots)
    safe_idx = jnp.clip(pos, 0, spec.num_atoms - 1)
    if atom_mask is not None:
        valid = jnp.logical_and(valid, atom_mask[safe_idx])
    values = lane_take(atom_values(atom_fn, spec.num_atoms, dtype), safe_idx)
    return jnp.where(valid, values, jnp.asarray(identity, dtype))


@jax.named_scope("windows")
def native_chunk_value_windows(spec: WorkSpec, part: Partition,
                               atom_fn: AtomFn, dtype=jnp.float32, *,
                               combiner: str = "sum",
                               atom_mask: jax.Array | None = None) -> jax.Array:
    """Per-chunk masked value windows via the chunk-walking Pallas kernel.

    The device-side counterpart of :func:`blocked_value_windows`: the same
    grid/queue discipline as :func:`native_chunk_tile_reduce`, with the
    kernel's ``emit="atoms"`` mode writing the masked window itself instead
    of per-tile bins.  Chunk boundaries equal the pure path's logical block
    boundaries (``part.atom_starts``) and both use the shared window layout,
    so both paths produce identical windows — the scatter step is shared
    and the paths stay bit-identical.
    """
    identity = _check_combiner(combiner, dtype)
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        raise ValueError("native path accumulates in float32")
    if not supports_native_execution(part):
        raise ValueError("partition does not support the native path "
                         "(see supports_native_execution)")
    window, _ = _window_sizes(spec, part)
    if spec.num_atoms == 0:
        return jnp.full((part.num_blocks, window_slots(window)), identity,
                        dtype)
    values = _masked_values(atom_fn, spec.num_atoms, dtype, identity,
                            atom_mask)
    return _native_windows(values, part.atom_starts, window, combiner,
                           _chunk_queue_view(part)[:2])


def _native_windows(values: jax.Array, starts: jax.Array, window: int,
                    combiner: str, queues: Tuple[jax.Array, jax.Array]
                    ) -> jax.Array:
    """``emit="atoms"`` kernel windows of ``values`` over chunk ``starts``,
    popped in the order of ``queues`` (``(block_chunks, counts)``)."""
    from repro.kernels.spmv_merge.kernel import chunk_walk_reduce

    block_chunks, counts = queues
    starts = starts.astype(jnp.int32)
    return chunk_walk_reduce(
        values, None, starts, jnp.zeros_like(starts),
        block_chunks.reshape(-1).astype(jnp.int32), counts.astype(jnp.int32),
        window=window, local_tiles=1, max_chunks=int(block_chunks.shape[1]),
        combiner=combiner, emit="atoms")


def _values_by_position(starts: jax.Array, windows: jax.Array,
                        num: int) -> jax.Array:
    """Read value windows back in position order: ``[num]`` values.

    Position ``k`` sits in the row of the chunk holding it, at slot ``k -
    origin[c]`` of the shared layout (:func:`_window_slot_view`): flat slot
    ``k + c * slots - origin[c]``.  That shift is constant over each chunk,
    so it is one prefix sum of its steps at the chunk starts.
    """
    starts = starts.astype(jnp.int32)
    slots = int(windows.shape[1])
    num_chunks = int(starts.shape[0]) - 1
    shift = (jnp.arange(num_chunks, dtype=jnp.int32) * slots
             - (starts[:-1] // WINDOW_ALIGN) * WINDOW_ALIGN)
    steps = jnp.zeros((num,), jnp.int32).at[starts[1:-1]].add(
        jnp.diff(shift), mode="drop")
    slot = (jnp.arange(num, dtype=jnp.int32) + shift[0]
            + jnp.cumsum(steps, dtype=jnp.int32))
    return lane_take(windows.reshape(-1), slot)


@jax.named_scope("scatter")
def scatter_value_windows(spec: WorkSpec, part: Partition,
                          windows: jax.Array, out_ids: jax.Array,
                          num_out: int, combiner: str = "sum") -> jax.Array:
    """Combine value windows by per-atom output ids (``[num_out]`` result).

    The second half of a scatter-reduce and the sibling of
    :func:`fixup_partials`: each atom's value is read back from its block's
    window (:func:`_values_by_position`) and reduced into segment
    ``out_ids`` of that atom (e.g. the edge's *destination* vertex in a
    push advance — the pull form of ``atomicMin`` by destination), in
    ascending atom order; ids equal to ``num_out`` are dropped.  Masked
    atoms carry the combiner's identity, so output segments nothing
    scatters to come back as the identity, exactly like untouched tiles of
    a tile-reduce.
    """
    values = _values_by_position(part.atom_starts, windows, spec.num_atoms)
    return _segment_reduce(combiner, values, out_ids, num_out + 1)[:-1]


# -- gather-compacted active-atom windows (sparse-frontier push mode) -------

@jax.named_scope("compact")
def compact_active_atoms(atom_mask: jax.Array,
                         capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Compact a bool atom mask into ``(idx [capacity], count)``.

    ``idx`` lists the active atom ids in ascending order, padded with
    ``num_atoms`` past the true count (so padded slots are recognisably out
    of range); ``count`` is the exact active-atom total, which callers
    compare against ``capacity`` to decide whether the compacted view is
    complete (past ``capacity`` the list is truncated, as
    ``jnp.nonzero(size=...)`` truncates).  One prefix sum over the mask
    gives each active atom its position and one scatter writes the list:
    no pass over ``capacity`` slots but the fill.
    """
    num_atoms = int(atom_mask.shape[0])
    position = jnp.cumsum(atom_mask, dtype=jnp.int32) - 1
    idx = jnp.full((capacity,), num_atoms, jnp.int32).at[
        jnp.where(atom_mask, position, capacity)].set(
            jnp.arange(num_atoms, dtype=jnp.int32), mode="drop")
    return idx, jnp.sum(atom_mask, dtype=jnp.int32)


def compact_rungs(capacity: int) -> Tuple[int, ...]:
    """The ladder of static compaction capacities, top rung first.

    The top rung is ``capacity``; each rung below holds half the one above
    (rounded up), down to the first at or below :data:`WINDOW_ALIGN`.  A
    traversal level runs the smallest rung that holds its active count
    (:func:`compact_rung_index`), so a sparse frontier pays for windows
    sized to itself, not to the densest push level the plan allows.
    """
    rungs = [max(int(capacity), 1)]
    while rungs[-1] > WINDOW_ALIGN:
        rungs.append(-(-rungs[-1] // 2))
    return tuple(rungs)


@jax.custom_batching.custom_vmap
def compact_rung_index(count: jax.Array, rungs: jax.Array) -> jax.Array:
    """Branch of the smallest rung of ``rungs`` (descending) that holds
    ``count`` active atoms; ``len(rungs)``, the masked fallback, when none
    does.

    Under ``jax.vmap`` the index stays unbatched: it is taken for the
    largest count over the lanes, a rung that holds every lane's atoms.  A
    batched index would turn the ``lax.switch`` it drives into a select
    that runs every rung for every lane.
    """
    holds = jnp.sum(count <= rungs, dtype=jnp.int32)
    return jnp.where(holds > 0, holds - 1, rungs.shape[0])


@compact_rung_index.def_vmap
def _compact_rung_index_vmap(axis_size, in_batched, count, rungs):
    if in_batched[0]:
        count = jnp.max(count, axis=0)
    return compact_rung_index(count, rungs), False


def compact_chunk_starts(num_chunks: int, capacity: int) -> jax.Array:
    """Even chunk boundaries over ``[0, capacity]`` compacted slots.

    Compacted atoms are interchangeable units of equal cost, so the even
    split *is* the balanced partition — frontier skew was flattened by the
    gather.
    """
    per = _compact_window(num_chunks, capacity)
    return jnp.minimum(jnp.arange(num_chunks + 1, dtype=jnp.int32) * per,
                       capacity)


def _compact_window(num_chunks: int, capacity: int) -> int:
    return -(-max(capacity, 1) // max(num_chunks, 1))


def _compact_slot_view(spec: WorkSpec, idx: jax.Array, num_chunks: int,
                       slots: int) -> Tuple[jax.Array, jax.Array]:
    """Shared slot -> atom addressing of the compacted windows.

    Returns ``(valid, safe_a)`` for the ``[num_chunks, slots]`` slot grid
    of the shared window layout over the compacted positions: whether the
    slot holds a real active atom (in-chunk and in-range), and a clamped
    atom id safe to gather with.
    """
    capacity = int(idx.shape[0])
    pos, in_chunk = _window_slot_view(
        compact_chunk_starts(num_chunks, capacity), slots)
    a = idx[jnp.clip(pos, 0, capacity - 1)]
    valid = jnp.logical_and(in_chunk, a < spec.num_atoms)
    return valid, jnp.clip(a, 0, max(spec.num_atoms - 1, 0))


def _compact_slots(part: Partition, idx: jax.Array) -> Tuple[int, int]:
    """(num_chunks, slots) of the compacted windows of ``idx`` over ``part``.

    One chunk per :data:`WINDOW_ALIGN` compacted slots, at most the
    partition's own chunk count: a small rung walks few chunks instead of
    spreading a handful of atoms over every chunk of the partition.
    """
    capacity = int(idx.shape[0])
    num_chunks = min(int(part.atom_starts.shape[0]) - 1,
                     -(-capacity // WINDOW_ALIGN))
    return num_chunks, window_slots(_compact_window(num_chunks, capacity))


@jax.named_scope("windows")
def blocked_compact_value_windows(spec: WorkSpec, part: Partition,
                                  atom_fn: AtomFn, idx: jax.Array,
                                  dtype=jnp.float32, *,
                                  combiner: str = "sum") -> jax.Array:
    """Per-chunk value windows over a *compacted* active-atom list (pure).

    The sparse-frontier sibling of :func:`blocked_value_windows`: the
    windows walk even chunk splits of the compacted positions
    (:func:`compact_chunk_starts`, :func:`_compact_slots`), and the slot at
    position ``k`` holds the value of atom ``idx[k]`` — only active atoms
    occupy slots, so the streamed window volume is the capacity, not the
    edge count.  Padded index slots (``idx`` carries ``num_atoms`` past the
    true active count) come back as the combiner's identity.
    """
    identity = _check_combiner(combiner, dtype)
    num_chunks, slots = _compact_slots(part, idx)
    valid, safe_a = _compact_slot_view(spec, idx, num_chunks, slots)
    values = lane_take(atom_values(atom_fn, spec.num_atoms, dtype), safe_a)
    return jnp.where(valid, values, jnp.asarray(identity, dtype))


@jax.named_scope("windows")
def native_compact_value_windows(spec: WorkSpec, part: Partition,
                                 atom_fn: AtomFn, idx: jax.Array,
                                 dtype=jnp.float32, *,
                                 combiner: str = "sum") -> jax.Array:
    """Compacted value windows via the chunk-walking kernel.

    The gather through the compacted index list runs in XLA before the
    launch (Mosaic has no in-kernel 1-D gather); the kernel then walks even
    chunk splits of the gathered values in its ``emit="atoms"`` mode —
    streaming only active atoms.  Compacted chunks all cost the same, so
    no queue discipline is needed: each grid step pops one chunk.  Chunk
    boundaries and layout equal the pure path's, so both paths produce
    identical windows and share one :func:`scatter_compact_windows` call.
    """
    identity = _check_combiner(combiner, dtype)
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        raise ValueError("native path accumulates in float32")
    if not supports_native_execution(part):
        raise ValueError("partition does not support the native path "
                         "(see supports_native_execution)")
    num_chunks, _ = _compact_slots(part, idx)
    capacity = int(idx.shape[0])
    active = idx < spec.num_atoms
    values = lane_take(atom_values(atom_fn, spec.num_atoms, dtype),
                       jnp.clip(idx, 0, max(spec.num_atoms - 1, 0)))
    values = jnp.where(active, values, jnp.asarray(identity, dtype))
    return _native_windows(values,
                           compact_chunk_starts(num_chunks, capacity),
                           _compact_window(num_chunks, capacity), combiner,
                           _single_chunk_queues(num_chunks))


@jax.named_scope("scatter")
def scatter_compact_windows(spec: WorkSpec, windows: jax.Array,
                            idx: jax.Array, out_ids: jax.Array,
                            num_out: int, combiner: str = "sum") -> jax.Array:
    """Combine compacted value windows by per-atom output ids.

    The compact-mode sibling of :func:`scatter_value_windows`: the value at
    compacted position ``k`` belongs to atom ``idx[k]``, whose output
    segment is that atom's ``out_ids`` entry; padded positions are dropped.
    Position ``k`` sits in chunk ``k // per`` of the even split, at slot
    ``k - origin`` of its row, so it is read back without a prefix sum.
    Active atoms keep their ascending order, so for the exact combiners —
    and exactly-summable values — results are bit-identical to the masked
    full-window scatter.
    """
    num_chunks, slots = int(windows.shape[0]), int(windows.shape[1])
    capacity = int(idx.shape[0])
    per = _compact_window(num_chunks, capacity)
    k = jnp.arange(capacity, dtype=jnp.int32)
    chunk = k // per
    slot = chunk * slots + k - (chunk * per // WINDOW_ALIGN) * WINDOW_ALIGN
    values = lane_take(windows.reshape(-1), slot)
    gid = jnp.where(idx < spec.num_atoms,
                    out_ids[jnp.clip(idx, 0, max(spec.num_atoms - 1, 0))],
                    num_out)
    return _segment_reduce(combiner, values, gid, num_out + 1)[:-1]


def execute_scatter_reduce(spec: WorkSpec, part: Partition, atom_fn: AtomFn,
                           out_ids: jax.Array, num_out: int,
                           dtype=jnp.float32, *,
                           path: ExecutionPath | str = ExecutionPath.AUTO,
                           combiner: str = "sum",
                           atom_mask: jax.Array | None = None,
                           compact_capacity: int | None = None) -> jax.Array:
    """One API over both scatter-reduce executors (the push-advance call).

    Balanced per-atom value production over ``spec``/``part`` (any schedule,
    either execution path — same routing rule as
    :func:`execute_tile_reduce`) followed by the shared destination scatter.
    ``out_ids`` (int32 ``[num_atoms]``) names each atom's output segment in
    ``[0, num_out)``; ``atom_mask`` drops atoms exactly as in a tile-reduce.
    Because both paths produce identical windows and share one
    :func:`scatter_value_windows` call, results are bit-identical across
    every schedule x path, and — for exact combiners (min/max) or
    exactly-summable values — to the corresponding pull-direction
    tile-reduce over the same edge multiset.

    ``compact_capacity`` (static int, requires ``atom_mask``) enables the
    gather-compacted window mode: the active atoms are compacted into an
    index list and only its slots are streamed — the ROADMAP's frontier
    compaction.  The windows' static size is a rung of
    :func:`compact_rungs`: the capacity, and halvings of it down to one
    window tile.  The exact active count picks the smallest rung that
    holds it (:func:`compact_rung_index`, unbatched under ``vmap``); the
    mask's prefix sum and the index list are built once, and a
    ``lax.switch`` runs that rung on the list's head under the scope
    ``compact.r<k>`` (``k = 0`` for the top rung).  When the count exceeds
    the capacity, a ``lax.cond`` falls back to the masked full-window mode
    instead, so any capacity is *correct*; a well-chosen one (see
    :func:`repro.core.balance.estimate_compact_capacity`) is merely fast.
    Every mode shares the segmented scatter in ascending atom order, so
    results stay bit-identical for exact combiners and exactly-summable
    values.
    """
    identity = _check_combiner(combiner, dtype)
    if spec.num_atoms == 0:
        return jnp.full((num_out,), identity, dtype)
    native_ok = (supports_native_execution(part)
                 and jnp.dtype(dtype) == jnp.dtype(jnp.float32))
    resolved = resolve_execution_path(path, native_supported=native_ok)

    @jax.named_scope("masked")
    def masked():
        if resolved == ExecutionPath.NATIVE:
            windows = native_chunk_value_windows(spec, part, atom_fn, dtype,
                                                 combiner=combiner,
                                                 atom_mask=atom_mask)
        else:
            windows = blocked_value_windows(spec, part, atom_fn, dtype,
                                            combiner=combiner,
                                            atom_mask=atom_mask)
        return scatter_value_windows(spec, part, windows, out_ids, num_out,
                                     combiner)

    if compact_capacity is None or atom_mask is None:
        return masked()
    rungs = compact_rungs(min(int(compact_capacity), spec.num_atoms))
    index = compact_rung_index(jnp.sum(atom_mask, dtype=jnp.int32),
                               jnp.asarray(rungs, jnp.int32))

    def rung(idx: jax.Array, k: int):
        @jax.named_scope(f"compact.r{k}")
        def run():
            head = idx[:rungs[k]]
            if resolved == ExecutionPath.NATIVE:
                windows = native_compact_value_windows(
                    spec, part, atom_fn, head, dtype, combiner=combiner)
            else:
                windows = blocked_compact_value_windows(
                    spec, part, atom_fn, head, dtype, combiner=combiner)
            return scatter_compact_windows(spec, windows, head, out_ids,
                                           num_out, combiner)
        return run

    @jax.named_scope("compact")
    def compact():
        idx, _ = compact_active_atoms(atom_mask, rungs[0])
        return jax.lax.switch(index, [rung(idx, k)
                                      for k in range(len(rungs))])

    return jax.lax.cond(index < len(rungs), compact, masked)


def execute_tile_reduce(spec: WorkSpec, part: Partition, atom_fn: AtomFn,
                        dtype=jnp.float32, *,
                        path: ExecutionPath | str = ExecutionPath.AUTO,
                        combiner: str = "sum",
                        atom_mask: jax.Array | None = None) -> jax.Array:
    """One API over both executors — the dispatcher the ops layers call.

    Routes any Partition (static, chunked_rr/chunked_lpt, adaptive) to the
    native Pallas chunk-walking kernel or the pure-JAX blocked executor.
    ``path="auto"`` prefers native exactly when the partition supports it
    (concrete span hints; invertible block map) *and* the requested dtype
    is float32 (the native kernel's accumulator); other dtypes fall back
    to the pure executor rather than raise.  ``combiner``/``atom_mask``
    (sum/min/max; frontier mask) apply identically on either path — this is
    what lets graph advance ride every schedule unchanged.
    """
    native_ok = (supports_native_execution(part)
                 and jnp.dtype(dtype) == jnp.dtype(jnp.float32))
    resolved = resolve_execution_path(path, native_supported=native_ok)
    if resolved == ExecutionPath.NATIVE:
        return native_chunk_tile_reduce(spec, part, atom_fn, dtype,
                                        combiner=combiner,
                                        atom_mask=atom_mask)
    return blocked_tile_reduce(spec, part, atom_fn, dtype,
                               combiner=combiner, atom_mask=atom_mask)


# ---------------------------------------------------------------------------
# Shard-local dispatch (multi-device: the same executors one level up)
# ---------------------------------------------------------------------------

#: Cross-device collective matching each combiner — the shard-level
#: continuation of a scatter reduce.  Exactly the pairing that keeps the
#: sharded result bit-identical to single-device: min/max collectives are
#: exact, and psum of disjoint per-shard contributions (every shard holds
#: identity except the edge owners) adds identity elements bit-exactly.
COMBINER_COLLECTIVE = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                       "max": jax.lax.pmax}


def execute_sharded_tile_reduce(spec: WorkSpec, part: Partition,
                                atom_fn: AtomFn, dtype=jnp.float32, *,
                                axis_name: str = "shard",
                                path: ExecutionPath | str = ExecutionPath.AUTO,
                                combiner: str = "sum",
                                atom_mask: jax.Array | None = None) -> jax.Array:
    """:func:`execute_tile_reduce` inside a ``shard_map`` body.

    The pull-direction shard contract: each shard's local spec owns *all*
    atoms (in-edges) of its own tiles (destinations), so the local reduce is
    already the final per-tile answer — no collective is needed and the
    result bits come from exactly the same executor call a single device
    makes.  ``axis_name`` is accepted (and ignored) so both directions share
    a call shape; it documents that this runs under a mesh axis.
    """
    del axis_name  # pull owns all in-edges of its tiles; purely local
    return execute_tile_reduce(spec, part, atom_fn, dtype, path=path,
                               combiner=combiner, atom_mask=atom_mask)


def execute_sharded_scatter_reduce(spec: WorkSpec, part: Partition,
                                   atom_fn: AtomFn, out_ids: jax.Array,
                                   num_out: int, dtype=jnp.float32, *,
                                   axis_name: str = "shard",
                                   path: ExecutionPath | str =
                                   ExecutionPath.AUTO,
                                   combiner: str = "sum",
                                   atom_mask: jax.Array | None = None,
                                   compact_capacity: int | None = None) -> jax.Array:
    """:func:`execute_scatter_reduce` inside a ``shard_map`` body.

    The push-direction shard contract: each shard streams only its own
    out-edges but their destinations land anywhere, so every shard produces
    a full ``[num_out]`` partial (identity at untouched destinations) and
    the partials combine across the mesh axis with the combiner's matching
    collective (:data:`COMBINER_COLLECTIVE`).  Per shard the pure/native
    paths stay bit-identical (same single-device dispatcher); the collective
    is exact for min/max and adds disjoint-support partials exactly for sum,
    so the sharded result matches single-device bitwise under the same
    conditions the two directions match each other.
    """
    partial = execute_scatter_reduce(spec, part, atom_fn, out_ids, num_out,
                                     dtype, path=path, combiner=combiner,
                                     atom_mask=atom_mask,
                                     compact_capacity=compact_capacity)
    return COMBINER_COLLECTIVE[combiner](partial, axis_name)
