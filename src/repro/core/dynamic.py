"""Dynamic load-balancing schedules (paper §3.2's "dynamic" half).

The paper's abstraction "aims to support both static and dynamic schedules";
the static four live in :mod:`repro.core.schedules`.  This module adds the
dynamic side, following Atos (arXiv 2112.00132): instead of computing one
final block assignment, *oversplit* the work into many more chunks than
processors and let a work queue drain them.  On TPU there is no in-kernel
queue, so the queue discipline is made static per input: the inspector runs
on the host (or in XLA, pre-launch), produces a chunk-level
:class:`~repro.core.schedules.Partition` — the same contract every executor
and Pallas kernel already consumes — and records the chunk -> physical block
assignment in ``Partition.block_map``.

Two schedules:

* :func:`chunked_partition` — Atos-style chunked work queue.  The WorkSpec
  is oversplit into ``chunk_factor * num_blocks`` chunks of roughly equal
  atom count; chunk boundaries snap to tile boundaries when one is close
  (so most chunks need no cross-chunk fixup) but heavy tiles are split
  mid-tile (so no chunk is ever larger than ~2x the target).  Chunks are
  assigned to physical blocks round-robin or greedily by
  longest-processing-time (LPT), the classic makespan heuristic.

* :func:`adaptive_partition` — two-phase "inspect then balance".  Phase 1
  inspects the cheap tile-mapped partition; if its atom imbalance is under
  ``imbalance_threshold`` it is returned unchanged (zero extra cost — the
  common case for regular workloads).  Otherwise phase 2 re-partitions with
  equal-atom cuts that stay tile-aligned everywhere except inside tiles too
  heavy to place on one block — only the tiles that exceed the threshold pay
  for the repartition.

Both partitioners prefer concrete (host) inputs — schedule construction is
an inspector that runs before kernel launch — but degrade gracefully under
tracing: snapping and cuts are pure jnp; only the LPT policy and the
adaptive early-exit need concrete sizes and fall back (to round-robin and
"always balance" respectively) when traced.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.balance import CHUNK_OVERHEAD, LANES
from repro.core.schedules import (Partition, Schedule, finalize_partition,
                                  tile_mapped_partition)
from repro.core.work import WorkSpec

#: Default oversplit factor: chunks per physical block (Atos uses 4-16).
DEFAULT_CHUNK_FACTOR = 4

#: Default adaptive trigger: re-balance when max block load > 1.5x mean.
DEFAULT_IMBALANCE_THRESHOLD = 1.5


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# Shared inspector: equal-atom cuts with tile-boundary snapping.
# ---------------------------------------------------------------------------

def _snapped_atom_cuts(spec: WorkSpec, num_cuts: int, quantum: int
                       ) -> jax.Array:
    """``num_cuts + 1`` non-decreasing atom boundaries covering all atoms.

    Cut ``c`` targets atom ``c * quantum`` and snaps to the nearest tile
    boundary when that boundary is within ``quantum // 2`` atoms; cuts inside
    heavier tiles stay mid-tile (the tile gets split).  Snap tolerance of
    half a quantum keeps the snapped sequence non-decreasing and bounds every
    span by ``2 * quantum``.
    """
    cuts = jnp.minimum(
        jnp.arange(num_cuts + 1, dtype=jnp.int32) * quantum, spec.num_atoms)
    if spec.num_tiles == 0 or spec.num_atoms == 0:
        return cuts
    tol = max(quantum // 2, 0)
    owner = jnp.clip(
        jnp.searchsorted(spec.tile_offsets, cuts, side="right") - 1,
        0, spec.num_tiles - 1).astype(jnp.int32)
    lo = spec.tile_offsets[owner]          # tile start at/before the cut
    hi = spec.tile_offsets[owner + 1]      # tile end at/after the cut
    d_lo = cuts - lo
    d_hi = hi - cuts
    snapped = jnp.where(
        (d_lo <= d_hi) & (d_lo <= tol), lo,
        jnp.where(d_hi <= tol, hi, cuts))
    # endpoints are structural, never snapped
    snapped = snapped.at[0].set(0).at[-1].set(spec.num_atoms)
    return snapped.astype(jnp.int32)


def _partition_from_atom_cuts(spec: WorkSpec, cuts: jax.Array,
                              schedule: Schedule, quantum: int,
                              block_map: Optional[jax.Array] = None,
                              num_physical_blocks: Optional[int] = None
                              ) -> Partition:
    """Assemble a Partition from atom boundaries (possibly mid-tile)."""
    tile_starts = (jnp.searchsorted(spec.tile_offsets, cuts, side="right")
                   .astype(jnp.int32) - 1)
    tile_starts = jnp.clip(tile_starts, 0, spec.num_tiles)
    spans = cuts[1:] - cuts[:-1]
    if _is_concrete(spans) and spans.shape[0]:
        items = max(int(jnp.max(spans)), 1)
    else:
        items = max(2 * quantum, 1)   # snap tolerance bounds spans by 2q
    aligned = False
    if _is_concrete(cuts):
        boundary = np.isin(np.asarray(cuts), np.asarray(spec.tile_offsets))
        aligned = bool(boundary.all())
    return finalize_partition(Partition(
        schedule=schedule, num_blocks=int(spans.shape[0]),
        items_per_block=items,
        atom_starts=cuts.astype(jnp.int32),
        tile_starts=tile_starts, tile_aligned=aligned,
        block_map=block_map,
        num_physical_blocks=num_physical_blocks))


# ---------------------------------------------------------------------------
# Chunked work queue (Atos-style).
# ---------------------------------------------------------------------------

def assign_chunks(chunk_cost: jax.Array, num_blocks: int,
                  policy: str = "lpt") -> jax.Array:
    """Map each chunk to a physical block.

    ``round_robin``: chunk ``c`` -> block ``c % num_blocks`` (static, works
    under tracing).  ``lpt``: sort chunks by cost descending, give each to
    the least-loaded block so far — the classic greedy makespan bound of
    4/3 OPT.  LPT needs concrete costs; traced inputs fall back to
    round-robin.
    """
    n = int(chunk_cost.shape[0])
    if policy == "round_robin" or not _is_concrete(chunk_cost):
        return jnp.arange(n, dtype=jnp.int32) % num_blocks
    if policy != "lpt":
        raise ValueError(f"unknown chunk policy: {policy}")
    cost = np.asarray(chunk_cost, np.int64)
    order = np.argsort(-cost, kind="stable")
    load = np.zeros(num_blocks, np.int64)
    out = np.zeros(n, np.int32)
    for c in order:
        b = int(np.argmin(load))
        out[c] = b
        load[b] += int(cost[c])
    return jnp.asarray(out)


def chunked_partition(spec: WorkSpec, num_blocks: int, *,
                      chunk_factor: int = DEFAULT_CHUNK_FACTOR,
                      policy: str = "lpt") -> Partition:
    """Atos-style chunked work queue as a static TPU schedule.

    Oversplits into ``chunk_factor * num_blocks`` chunks of ~equal atoms
    (tile-snapped; heavy tiles split), then assigns chunks to the
    ``num_blocks`` physical blocks.  The returned Partition has one entry
    per *chunk* — executors consume it unchanged and stay correct; the
    queue discipline lives in ``block_map`` and is what the cost model
    (and a sequential-grid TPU launch) pays.
    """
    num_blocks = max(int(num_blocks), 1)
    num_chunks = max(chunk_factor, 1) * num_blocks
    # never oversplit beyond one atom per chunk (keeps windows non-trivial)
    num_chunks = min(num_chunks, max(spec.num_atoms, 1))
    quantum = _ceil_div(max(spec.num_atoms, 1), num_chunks)
    cuts = _snapped_atom_cuts(spec, num_chunks, quantum)
    # LPT must balance what a block actually pays per chunk — lockstep steps
    # plus the constant queue-pop overhead (balancing raw atoms would let
    # every zero-cost chunk pile onto one block).
    spans = cuts[1:] - cuts[:-1]
    chunk_cost = -(-spans // LANES) + CHUNK_OVERHEAD
    block_map = assign_chunks(chunk_cost, num_blocks, policy)
    return _partition_from_atom_cuts(spec, cuts, Schedule.CHUNKED, quantum,
                                     block_map=block_map,
                                     num_physical_blocks=num_blocks)


# ---------------------------------------------------------------------------
# Adaptive inspect-then-balance.
# ---------------------------------------------------------------------------

# Serving-loop memoisation: ``adaptive_partition`` is an inspector, and a
# serving loop calls it per request — without a cache it re-inspects the
# workload every call even when the routing/shape recurs (and ``jit`` cannot
# help: the inspector needs concrete sizes, so it runs *outside* the traced
# computation).  Keyed by an exact content fingerprint of the offsets — not
# the autotuner's quantised shape bucket — because the partition's cut
# points depend on the actual offsets, not just their shape statistics.
_ADAPTIVE_CACHE: "OrderedDict[tuple, Partition]" = OrderedDict()
_ADAPTIVE_CACHE_CAPACITY = 256
_ADAPTIVE_CACHE_LOCK = threading.Lock()


def adaptive_inspection_count() -> int:
    """How many times the adaptive inspector actually ran (cache misses).

    Monotonic process-wide counter for regression tests: repeated calls on
    the same workload must not re-inspect.
    """
    return telemetry.counters().get("adaptive_inspections", 0)


def clear_adaptive_cache() -> None:
    with _ADAPTIVE_CACHE_LOCK:
        _ADAPTIVE_CACHE.clear()


def _workload_fingerprint(spec: WorkSpec) -> Optional[str]:
    """Exact (not quantised) content hash of a concrete WorkSpec."""
    if not _is_concrete(spec.tile_offsets):
        return None
    digest = hashlib.sha1(np.ascontiguousarray(
        np.asarray(spec.tile_offsets, np.int64)).tobytes()).hexdigest()
    return f"{spec.num_tiles}:{spec.num_atoms}:{digest}"


def adaptive_partition(spec: WorkSpec, num_blocks: int, *,
                       imbalance_threshold: float =
                       DEFAULT_IMBALANCE_THRESHOLD,
                       cache: bool = True) -> Partition:
    """Two-phase schedule: keep the cheap tile-mapped partition when it is
    balanced; re-partition (splitting only over-threshold tiles) when not.

    Built partitions are memoised per (workload fingerprint, num_blocks,
    threshold) — the analogue of the autotuner's schedule-choice cache, so
    a serving loop can call this per request without paying the inspector
    each time.  ``cache=False`` forces a fresh inspection.
    """
    num_blocks = max(int(num_blocks), 1)
    key = None
    if cache:
        fingerprint = _workload_fingerprint(spec)
        if fingerprint is not None:
            key = (fingerprint, num_blocks, float(imbalance_threshold))
            with _ADAPTIVE_CACHE_LOCK:
                hit = _ADAPTIVE_CACHE.get(key)
                if hit is not None:
                    _ADAPTIVE_CACHE.move_to_end(key)
                    return hit
    telemetry.count("adaptive_inspections")
    part = _adaptive_partition_uncached(spec, num_blocks,
                                        imbalance_threshold)
    if key is not None:
        with _ADAPTIVE_CACHE_LOCK:
            _ADAPTIVE_CACHE[key] = part
            while len(_ADAPTIVE_CACHE) > _ADAPTIVE_CACHE_CAPACITY:
                _ADAPTIVE_CACHE.popitem(last=False)
    return part


def _adaptive_partition_uncached(spec: WorkSpec, num_blocks: int,
                                 imbalance_threshold: float) -> Partition:
    phase1 = tile_mapped_partition(spec, num_blocks, Schedule.ADAPTIVE)
    if spec.num_atoms == 0 or spec.num_tiles == 0 or num_blocks == 1:
        return phase1
    if _is_concrete(phase1.atom_starts):
        loads = np.diff(np.asarray(phase1.atom_starts))
        mean = spec.num_atoms / num_blocks
        if loads.max() <= imbalance_threshold * max(mean, 1.0):
            return phase1              # inspector says: balanced already
    quantum = _ceil_div(spec.num_atoms, num_blocks)
    cuts = _snapped_atom_cuts(spec, num_blocks, quantum)
    return _partition_from_atom_cuts(spec, cuts, Schedule.ADAPTIVE, quantum)
