"""Work definition: atoms, tiles and tile sets (paper §3.1).

The paper maps sparse data structures onto a three-level vocabulary:

* **work atom** — a single schedulable unit of work (e.g. one non-zero of a
  sparse matrix, one routed (token, expert) pair of an MoE layer).
* **work tile** — a logical set of atoms (e.g. one matrix row, one expert).
  Tiles have *variable* cost; atoms are assumed equal-cost.
* **tile set** — the whole problem; tiles are independent and parallelizable.

On the GPU the paper expresses these as C++ iterators.  The TPU-native
encoding is a single *segment-offset array*: ``tile_offsets[t]`` is the index
of the first atom of tile ``t`` (so tile ``t`` owns atoms
``[tile_offsets[t], tile_offsets[t+1])``).  Every sparse format supported by
the framework lowers to this encoding, after which all load-balancing
schedules (:mod:`repro.core.schedules`) apply uniformly — the separation of
concerns that is the paper's core contribution.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class WorkSpec:
    """A tile set: ``num_tiles`` tiles over ``num_atoms`` atoms.

    ``tile_offsets`` is an int32 array of shape ``[num_tiles + 1]`` with
    ``tile_offsets[0] == 0`` and ``tile_offsets[-1] == num_atoms``.  Empty
    tiles (repeated offsets) are legal and common (e.g. empty matrix rows).

    ``num_atoms``/``num_tiles`` are *static* Python ints: schedules use them
    to size grids and blocks at trace time.
    """

    tile_offsets: jax.Array  # int32 [num_tiles + 1]
    num_atoms: int
    num_tiles: int

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.tile_offsets,), (self.num_atoms, self.num_tiles)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (tile_offsets,) = children
        num_atoms, num_tiles = aux
        return cls(tile_offsets=tile_offsets, num_atoms=num_atoms,
                   num_tiles=num_tiles)

    # -- constructors (the "input from sparse data structures" stage) -------
    @classmethod
    def from_segment_offsets(cls, offsets: jax.Array, *, num_atoms: int,
                             num_tiles: Optional[int] = None) -> "WorkSpec":
        offsets = jnp.asarray(offsets, jnp.int32)
        if num_tiles is None:
            num_tiles = int(offsets.shape[0]) - 1
        return cls(tile_offsets=offsets, num_atoms=int(num_atoms),
                   num_tiles=int(num_tiles))

    @classmethod
    def from_csr(cls, row_offsets: jax.Array, nnz: int) -> "WorkSpec":
        """CSR: atoms = non-zeros, tiles = rows (paper Listing 1)."""
        return cls.from_segment_offsets(row_offsets, num_atoms=nnz)

    @classmethod
    def from_segment_sizes(cls, sizes: jax.Array, *, num_atoms: int) -> "WorkSpec":
        """E.g. MoE: ``sizes[e]`` = number of tokens routed to expert ``e``."""
        sizes = jnp.asarray(sizes, jnp.int32)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes, dtype=jnp.int32)])
        return cls.from_segment_offsets(offsets, num_atoms=num_atoms,
                                        num_tiles=int(sizes.shape[0]))

    @classmethod
    def from_sorted_tile_ids(cls, tile_ids: jax.Array, *, num_tiles: int,
                             num_atoms: int) -> "WorkSpec":
        """COO-style: per-atom tile ids (must be sorted ascending)."""
        sizes = jnp.bincount(tile_ids, length=num_tiles).astype(jnp.int32)
        return cls.from_segment_sizes(sizes, num_atoms=num_atoms)

    # -- derived quantities --------------------------------------------------
    def atoms_per_tile(self) -> jax.Array:
        """The paper's ``atoms_per_tile`` transform iterator (Listing 1)."""
        return self.tile_offsets[1:] - self.tile_offsets[:-1]

    def atom_tile_ids(self) -> jax.Array:
        """Map atom index -> owning tile id, shape [num_atoms].

        ``tile_of(a) = max { t : tile_offsets[t] <= a }`` — the paper's
        per-thread ``get_tile(atom_id)`` binary search, done for every atom
        at once as a count: one mark per tile start, then a prefix sum.
        That is O(atoms + tiles) work; a vectorized binary search would
        gather ``num_atoms`` offsets once per halving step.
        """
        starts = self.tile_offsets[1:-1].astype(jnp.int32)
        marks = jnp.zeros((self.num_atoms,), jnp.int32).at[starts].add(
            1, mode="drop")
        return jnp.cumsum(marks, dtype=jnp.int32)

    def total_work(self) -> int:
        """Merge-path work measure: one unit per atom + one per tile."""
        return self.num_atoms + self.num_tiles


def validate_workspec(spec: WorkSpec) -> None:
    """Host-side structural validation (used by tests and data loaders)."""
    off = np.asarray(spec.tile_offsets)
    assert off.ndim == 1 and off.shape[0] == spec.num_tiles + 1, "offset shape"
    assert off[0] == 0, "offsets must start at 0"
    assert off[-1] == spec.num_atoms, "offsets must end at num_atoms"
    assert np.all(np.diff(off) >= 0), "offsets must be non-decreasing"
