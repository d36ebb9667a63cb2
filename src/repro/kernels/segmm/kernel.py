"""Segmented (grouped) matmul — the MoE expert GEMM, load-balanced.

The irregular workload: after top-k routing, expert ``e`` owns a *variable*
number of tokens.  In the paper's vocabulary the routed (token, expert) pairs
are **atoms**, experts are **tiles**, and the batch is the **tile set**; the
schedule must hand equal-size chunks to the compute units even though tile
sizes are wildly skewed (router collapse, domain shift).

TPU-native schedule (megablocks-style, built from our abstraction):
tokens are sorted by expert and each expert's segment padded up to a multiple
of the M-block; every grid block then owns exactly ``(bm, bn, bk)`` of work —
a *perfectly balanced* block-diagonal GEMM.  The only irregular object left
is the ``block -> expert`` map, an int32 vector computed by
``WorkSpec.from_segment_sizes`` + one searchsorted (the group-mapped
schedule's prefix-sum binning, lifted to the chip level), delivered to the
kernel via scalar prefetch so the right expert weight tile is DMA'd per
block.

Grid: ``(m_blocks, n_blocks, k_blocks)``, k innermost/sequential for
accumulation.  VMEM per block at (128, 128, 512): lhs 256 KB + rhs 256 KB +
acc 64 KB (f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.execute import pallas_call


def _segmm_kernel(block_expert_ref, lhs_ref, rhs_ref, out_ref):
    del block_expert_ref  # consumed by the index maps only
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(lhs_ref[...].astype(jnp.float32),
                            rhs_ref[0].astype(jnp.float32),
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk"))
def segmented_matmul(lhs_padded: jax.Array, rhs: jax.Array,
                     block_expert: jax.Array, *, bm: int = 128,
                     bn: int = 128, bk: int = 512) -> jax.Array:
    """``out[i*bm:(i+1)*bm] = lhs[i*bm:(i+1)*bm] @ rhs[block_expert[i]]``.

    ``lhs_padded``: ``[M_pad, K]`` tokens sorted by expert, group-padded so
    every M-block maps to exactly one expert.  ``rhs``: ``[E, K, N]``.
    ``block_expert``: int32 ``[M_pad // bm]``.
    """
    m_pad, k_dim = lhs_padded.shape
    _, _, n_dim = rhs.shape
    assert m_pad % bm == 0
    bk = min(bk, k_dim)
    bn = min(bn, n_dim)
    assert k_dim % bk == 0 and n_dim % bn == 0
    grid = (m_pad // bm, n_dim // bn, k_dim // bk)

    return pallas_call(
        _segmm_kernel,
        name="segmented_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, be: (i, k)),
                pl.BlockSpec((1, bk, bn), lambda i, j, k, be: (be[i], k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, be: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(block_expert, lhs_padded, rhs)


# ---------------------------------------------------------------------------
# Native chunk-walking variant (dynamic schedules on-device).
# ---------------------------------------------------------------------------

def _segmm_chunk_kernel(block_expert_ref, chunks_ref, counts_ref,
                        lhs_ref, rhs_ref, out_ref, *, bm: int,
                        max_chunks: int):
    """One physical block drains its queue of M-blocks inside the kernel.

    The queue discipline (round-robin / LPT-ordered pops, see
    ``repro.kernels.segmm.ops``) arrives as the scalar-prefetched
    ``chunks_ref`` row; each pop DMAs the chunk's LHS window (dynamic slice,
    static ``bm`` size), looks up its expert, and accumulates into the
    chunk's own output rows — no host-side block permutation and no
    un-permute gather, unlike the fallback path.
    """
    p = pl.program_id(1)
    k = pl.program_id(2)
    count = counts_ref[p]

    def pop(i, carry):
        @pl.when(i < count)
        def _process():
            c = chunks_ref[p * max_chunks + i]
            e = block_expert_ref[c]

            @pl.when(k == 0)
            def _zero():
                out_ref[pl.ds(c * bm, bm), :] = jnp.zeros(
                    (bm, out_ref.shape[1]), jnp.float32)

            lhs = lhs_ref[pl.ds(c * bm, bm), :].astype(jnp.float32)
            rhs = rhs_ref[pl.ds(e, 1), :, :][0].astype(jnp.float32)
            out_ref[pl.ds(c * bm, bm), :] += jnp.dot(
                lhs, rhs, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, max_chunks, pop, 0)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "max_chunks"))
def segmented_matmul_chunked(lhs_padded: jax.Array, rhs: jax.Array,
                             block_expert: jax.Array,
                             block_chunks_flat: jax.Array,
                             chunk_counts: jax.Array, *, bm: int = 128,
                             bn: int = 128, bk: int = 512,
                             max_chunks: int = 1) -> jax.Array:
    """Chunk-walking segmented matmul over ``P`` physical blocks.

    Same contract as :func:`segmented_matmul` plus the queue:
    ``block_chunks_flat`` int32 ``[P * max_chunks]`` lists each physical
    block's M-block chunks in pop order, ``chunk_counts`` int32 ``[P]`` the
    true queue lengths.  Every M-block appears in exactly one queue, so each
    output row block is written exactly once per (j, k) wave.  Output is in
    *original* (unpermuted) M-block order — bit-identical to
    :func:`segmented_matmul` on the identity queue.
    """
    m_pad, k_dim = lhs_padded.shape
    e_dim, _, n_dim = rhs.shape
    assert m_pad % bm == 0
    bk = min(bk, k_dim)
    bn = min(bn, n_dim)
    assert k_dim % bk == 0 and n_dim % bn == 0
    num_physical = int(chunk_counts.shape[0])
    # j outermost so each output block's visits are consecutive; p then k so
    # every queue finishes its k-accumulation before the next output wave.
    grid = (n_dim // bn, num_physical, k_dim // bk)

    return pallas_call(
        functools.partial(_segmm_chunk_kernel, bm=bm, max_chunks=max_chunks),
        name="segmented_matmul_chunked",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((m_pad, bk), lambda j, p, k, *_: (0, k)),
                pl.BlockSpec((e_dim, bk, bn), lambda j, p, k, *_: (0, k, j)),
            ],
            out_specs=pl.BlockSpec((m_pad, bn), lambda j, p, k, *_: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(block_expert, block_chunks_flat, chunk_counts, lhs_padded, rhs)
