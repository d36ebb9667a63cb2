"""Seeded graph generators of the benchmark's configurations.

Both generators return the symmetric CSR the system under test consumes, as
NumPy arrays: ``row_offsets`` int32 ``[V+1]``, ``col_indices`` int32 ``[E]``
(sorted within each row) and ``weights`` float32 ``[E]`` in [0, 1), both
directions of an undirected edge sharing one weight.  Self-loops and
duplicate edges are removed.

* ``kronecker``: the Graph500 generator (specification, "Graph Generation"):
  ``edge_factor * 2**scale`` endpoint pairs, each bit of each endpoint drawn
  from the initiator ``(A, B, C, D)``, then a random vertex permutation.
* ``urand``: the GAP Benchmark Suite's "Urand" (arXiv:1508.03619): both
  endpoints uniform over ``2**scale`` vertices.

The endpoint bits are drawn on the device (JAX's threefry, the same bits on
any backend); the permutation, the removal of loops and duplicates and the
CSR are NumPy on the host, where sorting costs no compilation.  A weight is
a hash of its edge's two ends and the seed, computed on the device (in
blocks of edges for a CSR kept on the host), so both directions get the same
one without a sort.
A configuration may state ``undirected_edges``, the distinct count its seed
gives; a graph that differs from it is refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GENERATORS = ("kronecker", "urand")


@functools.partial(jax.jit, static_argnames=("generator", "scale", "draws",
                                             "initiator"))
def _draw_pairs(key, *, generator: str, scale: int, draws: int,
                initiator: tuple):
    if generator == "urand":
        k1, k2 = jax.random.split(key)
        n = 1 << scale
        return (jax.random.randint(k1, (draws,), 0, n, jnp.int32),
                jax.random.randint(k2, (draws,), 0, n, jnp.int32))
    a, b, c = initiator
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)

    def bit(i, st):
        src, dst = st
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        ii = jax.random.uniform(k1, (draws,)) > ab
        jj = jax.random.uniform(k2, (draws,)) > jnp.where(ii, c_norm, a_norm)
        return (src | (ii.astype(jnp.int32) << i),
                dst | (jj.astype(jnp.int32) << i))

    zeros = jnp.zeros((draws,), jnp.int32)
    return jax.lax.fori_loop(0, scale, bit, (zeros, zeros))


def _mix32(x):
    """A 32-bit integer hash (lowbias32): a bijection that scatters bits."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


#: Edges per block of :func:`edge_weights`: a block's temporaries take
#: tens of MiB on the device, whatever the graph's size.
WEIGHT_BLOCK = 1 << 22


@jax.jit
def _weight_block(offsets, cols, start, salt):
    """Weights of the edges ``start, start + 1, ...`` whose columns are
    ``cols``; positions past the last edge give values nobody reads."""
    pos = start + jnp.arange(cols.shape[0], dtype=offsets.dtype)
    rows = jnp.searchsorted(offsets, pos, side="right") - 1
    lo = jnp.minimum(rows, cols).astype(jnp.uint32)
    hi = jnp.maximum(rows, cols).astype(jnp.uint32)
    h = _mix32(lo ^ _mix32(hi ^ salt))
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def edge_weights(offsets, cols, salt):
    """Uniform float32 weights in [0, 1), one per undirected edge: a hash
    of the edge's two ends and ``salt``, so both directions agree.

    Where the CSR is on a device (JAX arrays), one pass there gives the
    weights on that device.  Host (NumPy) arrays get them in blocks of
    :data:`WEIGHT_BLOCK` edges, computed on the default device (the last
    block padded, so one program serves every block) and each brought back
    to the host: the device holds the row offsets and one block, never an
    array of all the edges.  Every weight depends on its own edge alone, so
    the blocks give the bits one pass would.
    """
    salt = jnp.uint32(salt)
    if isinstance(cols, jax.Array):
        return _weight_block(offsets, cols, jnp.zeros((), offsets.dtype), salt)
    e = cols.shape[0]
    out = np.empty(e, np.float32)
    size = max(1, min(WEIGHT_BLOCK, e))
    offsets = jnp.asarray(offsets)
    for start in range(0, e, size):
        part = cols[start:start + size]
        if part.size < size:
            part = np.concatenate(
                [part, np.zeros(size - part.size, part.dtype)])
        w = _weight_block(offsets, jnp.asarray(part),
                          jnp.asarray(start, offsets.dtype), salt)
        out[start:start + size] = np.asarray(w)[:min(size, e - start)]
    return out


def _symmetric_csr(und: np.ndarray, scale: int):
    """CSR ``(row_offsets, col_indices)`` of the undirected edges
    ``(lo << scale) | hi``, ``lo < hi``, given sorted."""
    n, mask = 1 << scale, (1 << scale) - 1
    rev = np.sort(((und & mask) << scale) | (und >> scale))
    # two sorted runs: the stable sort merges them
    keys = np.sort(np.concatenate([und, rev]), kind="stable")
    offsets = np.searchsorted(keys, np.arange(n + 1, dtype=np.int64) << scale)
    return offsets.astype(np.int32), (keys & mask).astype(np.int32)


def generate(cfg: dict, seed: int):
    """The configuration's graph drawn from ``seed``.

    Returns ``(row_offsets, col_indices, salt)``: the CSR as NumPy arrays,
    and the value :func:`edge_weights` draws the weights from.
    """
    generator = cfg["generator"]
    if generator not in GENERATORS:
        raise ValueError(f"unknown generator {generator!r}; "
                         f"expected one of {GENERATORS}")
    scale = int(cfg["scale"])
    n = 1 << scale
    words = np.random.SeedSequence([abs(int(seed)), int(seed < 0)]) \
        .generate_state(4, np.uint32)
    key = jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                             int(words[1]))
    src, dst = (np.asarray(x, np.int64) for x in _draw_pairs(
        key, generator=generator, scale=scale,
        draws=int(cfg["edge_factor"]) << scale,
        initiator=tuple(float(x) for x in cfg.get("initiator", (0,) * 4)[:3])))
    if generator == "kronecker":
        perm = np.random.default_rng(words[2]).permutation(n)
        src, dst = perm[src], perm[dst]
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    und = np.unique((lo << scale) | hi)
    stated = cfg.get("undirected_edges", und.size)
    if und.size != stated:
        raise ValueError(f"seed {seed} gives {und.size} distinct edges, the "
                         f"configuration states {stated}")
    offsets, cols = _symmetric_csr(und, scale)
    return offsets, cols, np.uint32(words[3])
