"""Merge-path SpMV as a Pallas TPU kernel.

TPU reformulation of Merrill & Garland merge-path (paper §5.2.1)
----------------------------------------------------------------
The CUDA kernel gives each thread an equal share of ``rows + nnz`` work items
and lets each thread binary-search its (row, nnz) start coordinate.  TPU grid
blocks need *static* VMEM windows, so we make the merge decomposition
explicit instead of searched:

1.  Build the **merged work-item stream** of length ``rows + nnz`` in XLA:
    atom ``a`` (one non-zero) sits at stream position ``a + row(a)``; the
    end-marker of row ``r`` sits at ``row_offsets[r+1] + r``.  This is
    exactly the merge path — a bijection onto ``[0, rows + nnz)`` — realized
    as one scatter.  Atom positions carry ``vals[a] * x[col[a]]``; markers
    carry ``0``.  Every position carries its global row id.
2.  Each Pallas grid block consumes a **static** window of ``block_items``
    stream items — the uniform diagonal split, so every block does identical
    work (the merge-path guarantee: a block touches at most
    ``block_items + 1`` rows, no matter how skewed the matrix).
3.  Inside the block, the per-row reduction is a one-hot contraction
    ``values[1, W] . onehot[R_LOC, W]^T`` on the **MXU** — the TPU analogue
    of the warp-cooperative segmented reduction.
4.  Rows crossing block boundaries are resolved by a scatter-add **fixup**
    over the per-block partials (Merrill's "segmented fixup" pass; TPU grid
    blocks must not order-depend, so the fixup is a separate tiny reduction).

Every block is a ``(1, 1, n)`` slice of a ``(G, 1, n)`` array: its last two
dimensions equal the array's, which is what Mosaic's tiling rule asks of a
block whose second-minor size is not a multiple of 8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.execute import WINDOW_ALIGN, pallas_call, window_slots

_SUBLANES, _LANES = 8, 128
assert WINDOW_ALIGN == _SUBLANES * _LANES   # one f32 VMEM tile per DMA


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _spmv_block_kernel(row_base_ref, vals_ref, rows_ref, out_ref, *,
                       r_loc: int):
    """One merge-path block: masked one-hot MXU contraction."""
    b = pl.program_id(0)
    local = rows_ref[0] - row_base_ref[b]                          # [1, W]
    # Rows outside [0, r_loc) (markers/padding carry value 0 anyway) simply
    # match no one-hot row — no explicit mask needed.
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (r_loc, 1), 0)
              == local).astype(jnp.float32)                        # [R, W]
    out_ref[0] = jax.lax.dot_general(
        vals_ref[0], onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                        # [1, R]


@functools.partial(jax.jit, static_argnames=("num_rows", "block_items"))
def spmv_merge_stream(stream_vals: jax.Array, stream_rows: jax.Array,
                      row_base: jax.Array, *, num_rows: int,
                      block_items: int = 512) -> jax.Array:
    """Run the blocked kernel over a pre-built merge stream.

    ``stream_vals`` f32 ``[G * block_items]`` (zero at markers/padding),
    ``stream_rows`` int32 ``[G * block_items]`` (global row per item),
    ``row_base`` int32 ``[G]`` (first row touched by each block).
    Returns dense ``y`` of shape ``[num_rows]``.
    """
    total = stream_vals.shape[0]
    assert total % block_items == 0
    grid = total // block_items
    r_loc = _round_up(block_items + 1, 128)
    block = pl.BlockSpec((1, 1, block_items), lambda b, rb: (b, 0, 0))

    partials = pallas_call(
        functools.partial(_spmv_block_kernel, r_loc=r_loc),
        name="spmv_merge_stream",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[block, block],
            out_specs=pl.BlockSpec((1, 1, r_loc), lambda b, rb: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((grid, 1, r_loc), jnp.float32),
    )(row_base, stream_vals.astype(jnp.float32).reshape(grid, 1, -1),
      stream_rows.astype(jnp.int32).reshape(grid, 1, -1))

    # Fixup: combine cross-block partial rows (scatter-add over partials).
    gids = row_base[:, None] + jnp.arange(r_loc, dtype=jnp.int32)[None, :]
    gids = jnp.where(gids < num_rows, gids, num_rows)
    y = jax.ops.segment_sum(partials.reshape(-1), gids.reshape(-1),
                            num_segments=num_rows + 1)
    return y[:-1]


# ---------------------------------------------------------------------------
# Native chunk-walking executor (dynamic schedules on-device).
# ---------------------------------------------------------------------------

#: Identity element per combiner, mirrored from
#: ``repro.core.execute.COMBINER_IDENTITY``.
_IDENTITY = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}
_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
_REDUCE = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def _as_tiles(x: jax.Array, fill) -> jax.Array:
    """``[..., A]`` -> ``[..., ceil(A / WINDOW_ALIGN) * 8, 128]``, padded with
    ``fill``."""
    num = int(x.shape[-1])
    pad = _round_up(max(num, 1), WINDOW_ALIGN) - num
    x = jnp.concatenate(
        [x, jnp.full(x.shape[:-1] + (pad,), fill, x.dtype)], axis=-1)
    return x.reshape(x.shape[:-1] + (-1, _LANES))


def _chunk_walk_kernel(atom_starts_ref, tile_starts_ref, chunks_ref,
                       counts_ref, *refs, max_chunks: int, combiner: str,
                       emit: str, bin_rows: int):
    """Grid step ``(b, p, i)``: lane ``b``'s physical block ``p`` pops its
    ``i``-th chunk.

    The queue discipline of :mod:`repro.core.dynamic` is delivered as the
    scalar-prefetched ``chunks_ref`` row (the inverted, padded view of
    ``Partition.block_map``); the output block of step ``(b, p, i)`` is the
    popped chunk's own row of lane ``b``, so steps past the queue's length
    (which map to a spare row) write nothing anyone reads.

    The operands stay in HBM as ``[rows, 128]`` views (values with a
    leading lane axis).  The chunk's atoms ``[atom_starts[c],
    atom_starts[c+1])`` are walked one ``(8, 128)`` tile at a time, DMA'd
    from the tile that holds the chunk's first atom; each slot is masked by
    its *global* atom index, so no read starts mid-tile.

    ``emit="tiles"`` reduces the walked values into local tile bins
    ``tids - tile_starts[c]`` (the output row, ``bin_rows x 128`` bins):
    each 128-atom column of a tile is compared against the bins of the tile
    rows its atoms reach, and the masked values are reduced over atoms with
    the combiner.  ``emit="atoms"`` writes the masked tiles themselves: the
    output row holds the values of the atoms from the chunk's window origin
    on, identity outside the chunk.
    """
    if emit == "tiles":
        vals_hbm, tids_hbm, out_ref, vals_buf, tids_buf, bounds, sem = refs
    else:
        vals_hbm, out_ref, vals_buf, sem = refs
    identity = _IDENTITY[combiner]
    b, p, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    out_ref[...] = jnp.full(out_ref.shape, identity, jnp.float32)

    @pl.when(i < counts_ref[p])
    def _walk():
        c = chunks_ref[p * max_chunks + i]
        base, end = atom_starts_ref[c], atom_starts_ref[c + 1]
        first = base // WINDOW_ALIGN
        slot = (jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
                * _LANES
                + jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 1))

        def fetch(src, dst, t):
            row = pl.multiple_of((first + t) * _SUBLANES, _SUBLANES)
            copy = pltpu.make_async_copy(src.at[pl.ds(row, _SUBLANES)], dst,
                                         sem)
            copy.start()
            copy.wait()

        def tile(t, carry):
            fetch(vals_hbm.at[b], vals_buf, t)
            atom = (first + t) * WINDOW_ALIGN + slot
            ok = jnp.logical_and(atom >= base, atom < end)
            vals = jnp.where(ok, vals_buf[...], identity)
            if emit == "atoms":
                out_ref[pl.ds(pl.multiple_of(t * _SUBLANES, _SUBLANES),
                              _SUBLANES), :] = vals
                return carry
            fetch(tids_hbm, tids_buf, t)
            local = jnp.where(ok, tids_buf[...] - tile_starts_ref[c], -1)
            # bin rows this tile reaches (atoms are sorted by tile)
            bounds[0] = jnp.min(jnp.where(ok, local, bin_rows * _LANES))
            bounds[1] = jnp.max(local)
            vals_t, local_t = vals.T, local.T                       # [128, 8]
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

            def bin_row(r, carry):
                bins = r * _LANES + lane                             # [1, 128]
                acc = out_ref[pl.ds(r, 1), :]
                for k in range(_SUBLANES):
                    hit = local_t[:, k:k + 1] == bins            # [128, 128]
                    acc = _COMBINE[combiner](acc, _REDUCE[combiner](
                        jnp.where(hit, vals_t[:, k:k + 1], identity),
                        axis=0, keepdims=True))
                out_ref[pl.ds(r, 1), :] = acc
                return carry

            jax.lax.fori_loop(bounds[0] // _LANES, bounds[1] // _LANES + 1,
                              bin_row, 0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(end, WINDOW_ALIGN) - first, tile, 0)


def _launch(vals, tids, atom_starts, tile_starts, block_chunks_flat,
            chunk_counts, *, window: int, local_tiles: int, max_chunks: int,
            combiner: str, emit: str) -> jax.Array:
    """The kernel over lanes ``vals [B, A]``; returns ``[B, C, cols]``."""
    lanes = int(vals.shape[0])
    num_chunks = int(atom_starts.shape[0]) - 1
    num_physical = int(chunk_counts.shape[0])
    operands = [_as_tiles(vals.astype(jnp.float32), 0.0)]
    scratch = [pltpu.VMEM((_SUBLANES, _LANES), jnp.float32)]
    if emit == "tiles":
        rows = -(-max(local_tiles, 1) // _LANES)
        operands.append(_as_tiles(tids.astype(jnp.int32), 0))
        scratch += [pltpu.VMEM((_SUBLANES, _LANES), jnp.int32),
                    pltpu.SMEM((2,), jnp.int32)]
    else:
        rows = window_slots(window) // _LANES

    def out_row(b, p, i, starts, tstarts, chunks, counts):
        # steps past the queue's end write the spare row ``num_chunks``
        return (b, jnp.where(i < counts[p], chunks[p * max_chunks + i],
                             num_chunks), 0, 0)

    out = pallas_call(
        functools.partial(_chunk_walk_kernel, max_chunks=max_chunks,
                          combiner=combiner, emit=emit, bin_rows=rows),
        name="chunk_walk_reduce",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(lanes, num_physical, max_chunks),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(operands),
            out_specs=pl.BlockSpec((None, None, rows, _LANES), out_row),
            scratch_shapes=scratch + [pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, num_chunks + 1, rows, _LANES),
                                       jnp.float32),
    )(atom_starts, tile_starts, block_chunks_flat, chunk_counts, *operands)
    out = out[:, :num_chunks].reshape(lanes, num_chunks, rows * _LANES)
    return out[..., :local_tiles] if emit == "tiles" else out


@functools.partial(jax.jit, static_argnames=("window", "local_tiles",
                                             "max_chunks", "combiner",
                                             "emit"))
def chunk_walk_reduce(vals: jax.Array, tids: jax.Array | None,
                      atom_starts: jax.Array, tile_starts: jax.Array,
                      block_chunks_flat: jax.Array, chunk_counts: jax.Array,
                      *, window: int, local_tiles: int, max_chunks: int,
                      combiner: str = "sum", emit: str = "tiles"
                      ) -> jax.Array:
    """Per-chunk partial tile reductions via the chunk-walking Pallas kernel.

    ``vals`` f32 ``[A]`` (per-atom values, the frontier mask already applied
    as the combiner's identity), ``tids`` int32 ``[A]`` (owning tile per
    atom), ``atom_starts``/``tile_starts`` int32 ``[C + 1]`` chunk
    boundaries, ``block_chunks_flat`` int32 ``[P * max_chunks]`` (row ``p``
    = physical block ``p``'s queue), and ``chunk_counts`` int32 ``[P]``.
    Grid = ``(lanes, P, max_chunks)``; every chunk row of the ``[C,
    local_tiles]`` result is written by exactly the step that pops it.  The
    caller resolves cross-chunk partial tiles with the shared fixup (see
    :func:`repro.core.execute.fixup_partials`).  ``window`` bounds the atoms
    of any chunk.

    ``emit="atoms"`` returns ``[C, window_slots(window)]`` masked value
    windows instead of per-tile partials, each row starting at its chunk's
    window origin (the push-direction advance; the caller combines by
    per-atom destination ids — see
    :func:`repro.core.execute.scatter_value_windows`).  ``tids`` is unused
    (pass ``None``).

    Under ``jax.vmap`` over ``vals`` (serving lanes, multi-source BFS) the
    lanes become the kernel's leading grid axis, sharing one copy of the
    chunk structure and ``tids``: Mosaic cannot batch an HBM operand itself.
    Lanes that carry their own chunk structure run one launch each.
    """
    if combiner not in _IDENTITY:
        raise ValueError(f"unknown combiner: {combiner!r}")
    if emit not in ("tiles", "atoms"):
        raise ValueError(f"unknown emit mode: {emit!r}")
    launch = functools.partial(_launch, window=window,
                               local_tiles=local_tiles,
                               max_chunks=max_chunks, combiner=combiner,
                               emit=emit)
    shared = (atom_starts, tile_starts, block_chunks_flat, chunk_counts)
    if emit == "tiles":
        shared = (tids,) + shared

    @jax.custom_batching.custom_vmap
    def run(vals, *shared):
        tids, rest = (shared[0], shared[1:]) if emit == "tiles" \
            else (None, shared)
        lead = vals.shape[:-1]
        out = launch(vals.reshape((-1,) + vals.shape[-1:]), tids, *rest)
        return out.reshape(lead + out.shape[1:])

    @run.def_vmap
    def _lanes(axis_size, in_batched, vals, *shared):
        if not any(in_batched[1:]):
            return run(vals, *shared), True
        # per-lane chunk structures (a vmapped cond batches every operand
        # of its branches): one launch per lane
        args = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, b in zip((vals,) + shared, in_batched)]
        return jax.lax.map(lambda a: run(*a), tuple(args)), True

    return run(vals, *shared)
