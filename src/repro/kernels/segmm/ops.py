"""Jitted public wrapper: unsorted routed tokens in, expert outputs out.

This is the "work definition" stage for the MoE workload: atoms = routed
tokens, tiles = experts.  The wrapper builds the sorted, group-padded layout
and the block->expert map (the schedule), then invokes the balanced Pallas
GEMM.  All shapes are static: the padded capacity is the worst case
``T + E * (bm - 1)`` rounded up, so the same compiled kernel serves every
routing outcome — a requirement for TPU serving.

Schedule policies (the dynamic-scheduling hook): the chunk -> block queue
discipline of :mod:`repro.core.dynamic` shows up here over the M-blocks.
``"group_mapped"`` keeps expert order; ``"chunked_rr"`` deals M-blocks
round-robin across a pool of physical blocks (Atos queue with round-robin
pops); ``"chunked_lpt"`` deals them heaviest-expert-first (greedy LPT).
All policies are algebraically identical — tests assert bit-equality —
which is exactly the paper's schedule/execution separation.

Execution paths (see :class:`repro.core.execute.ExecutionPath`): the
chunked policies execute **natively** by default — the queue per physical
block is scalar-prefetched into the chunk-walking Pallas kernel
(:func:`repro.kernels.segmm.kernel.segmented_matmul_chunked`), which walks
its M-blocks *inside* the kernel with no host-side permutation.  The
``"pure"`` path realizes the same queue as a host-side block permutation
feeding the plain kernel (PR-1 behavior, kept as the executable spec the
native path is tested against).  ``"auto"`` consults the cost-model
autotuner when the routing is concrete (eager inspector) and falls back to
``"group_mapped"`` under tracing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execute import ExecutionPath, resolve_execution_path
from repro.kernels.segmm import kernel as _kernel

SCHEDULE_POLICIES = ("group_mapped", "chunked_rr", "chunked_lpt")

#: Physical-block pool the chunked policies drain their M-block queues with.
NUM_QUEUES = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def resolve_schedule(expert_of_token, num_experts: int,
                     num_blocks: int = 64, *, measure=None) -> str:
    """Map the autotuner's choice onto a segmm block-order policy.

    Inspector step: needs concrete routing.  Under tracing (inside a jitted
    train step) returns the static default.

    ``measure`` is the measured-cost feedback knob (docs/autotune.md): a
    callable ``(plan) -> median_us`` timing one candidate plan on the
    caller's actual GEMM.  Forwarded to
    :func:`repro.core.autotune.select_plan` — consulted only when
    ``REPRO_AUTOTUNE_MEASURE`` is on, in which case the choice is re-ranked
    by measurement (the routing histogram's cache record then carries v2
    measured medians).  ``None`` keeps the model-only schedule-level
    selection of PR 2.
    """
    if isinstance(expert_of_token, jax.core.Tracer):
        return "group_mapped"
    from repro.core.autotune import (measurement_enabled, select_plan,
                                     select_schedule)
    from repro.core.schedules import Schedule
    from repro.core.work import WorkSpec

    counts = np.bincount(np.asarray(expert_of_token),
                         minlength=num_experts)[:num_experts]
    spec = WorkSpec.from_segment_sizes(jnp.asarray(counts, jnp.int32),
                                       num_atoms=int(counts.sum()))
    if measure is not None and measurement_enabled():
        chosen = select_plan(spec, num_blocks, measure=measure).schedule
    else:
        chosen = select_schedule(spec, num_blocks)
    return "chunked_lpt" if chosen == Schedule.CHUNKED else "group_mapped"


def plan_policy(plan) -> tuple[str, str]:
    """(schedule policy, path) a core autotuner plan maps onto for segmm.

    Only the chunked schedule has a native queue discipline here; every
    other core schedule executes as the group-mapped baseline.  Shared by
    the default measured-mode closure and tests.
    """
    policy = ("chunked_lpt" if str(plan.schedule) == "chunked"
              else "group_mapped")
    path = "native" if (policy != "group_mapped"
                        and str(plan.path) == "native") else "pure"
    return policy, path


def level_grouped_matmul(tokens: jax.Array, op_of_token: jax.Array,
                         rhs: jax.Array, *, num_ops: int, plan=None,
                         schedule: str | None = None,
                         path: str | None = None, bm: int = 8,
                         bn: int = 128, bk: int = 512) -> jax.Array:
    """Per-level dense evaluation entry for the wavefront scheduler.

    A DAG level is the MoE routing problem with ops for experts: atoms =
    nodes awaiting evaluation this level, tiles = per-node operator types,
    and the whole level runs as ONE balanced segmented matmul instead of
    per-node recursion.  ``plan`` is a core (schedule, path) object — e.g.
    the wavefront dependency :class:`~repro.sparse.advance.AdvancePlan` —
    whose choice is mapped onto the segmm block-order policies via
    :func:`plan_policy`, so the level GEMM rides the same schedule decision
    as the dependency advance; explicit ``schedule``/``path`` strings
    override.  Every output row depends only on its own token row, so the
    result is bitwise-invariant across all policies and paths — the
    property the wavefront conformance matrix leans on.  Called from
    inside a ``lax.while_loop`` body: all shape logic is traceable and the
    M-block default is sized for node counts, not token batches.
    """
    if plan is not None:
        p_sched, p_path = plan_policy(plan)
        schedule = schedule or p_sched
        path = path or p_path
    return _grouped_matmul(tokens, op_of_token, rhs, num_experts=num_ops,
                           bm=bm, bn=bn, bk=bk,
                           schedule=schedule or "group_mapped",
                           path=path or "pure")


@functools.partial(jax.jit, static_argnames=("num_experts", "bm", "bn", "bk",
                                             "schedule", "path"))
def _grouped_matmul(tokens: jax.Array, expert_of_token: jax.Array,
                    rhs: jax.Array, *, num_experts: int, bm: int,
                    bn: int, bk: int, schedule: str, path: str) -> jax.Array:
    t_dim, k_dim = tokens.shape
    e_dim = num_experts
    m_pad = _round_up(t_dim + e_dim * (bm - 1), bm)

    # --- schedule construction (group-mapped prefix-sum binning) ----------
    order = jnp.argsort(expert_of_token)                     # sort atoms
    sorted_e = expert_of_token[order]
    sizes = jnp.bincount(expert_of_token, length=e_dim)
    offsets = jnp.concatenate([jnp.zeros((1,), sizes.dtype),
                               jnp.cumsum(sizes)])
    padded_sizes = ((sizes + bm - 1) // bm) * bm
    padded_offsets = jnp.concatenate([jnp.zeros((1,), sizes.dtype),
                                      jnp.cumsum(padded_sizes)])
    rank = jnp.arange(t_dim) - offsets[sorted_e]             # rank in group
    pos_sorted = (padded_offsets[sorted_e] + rank).astype(jnp.int32)

    lhs_padded = jnp.zeros((m_pad, k_dim), tokens.dtype)
    lhs_padded = lhs_padded.at[pos_sorted].set(tokens[order])

    nblk = m_pad // bm
    block_start = jnp.arange(nblk, dtype=jnp.int32) * bm
    block_expert = (jnp.searchsorted(padded_offsets, block_start,
                                     side="right").astype(jnp.int32) - 1)
    block_expert = jnp.clip(block_expert, 0, e_dim - 1)

    # --- queue discipline: M-block pop order -------------------------------
    if schedule == "chunked_rr":
        # round-robin pops: deal blocks across the queues in index order
        pop_order = jnp.arange(nblk, dtype=jnp.int32)
    elif schedule == "chunked_lpt":
        # greedy LPT: heaviest experts' blocks dealt first (stable, traceable)
        pop_order = jnp.argsort(-sizes[block_expert],
                                stable=True).astype(jnp.int32)
    elif schedule == "group_mapped":
        pop_order = jnp.arange(nblk, dtype=jnp.int32)
    else:
        raise ValueError(f"unknown segmm schedule: {schedule}")

    if path == "native" and schedule in ("chunked_rr", "chunked_lpt"):
        # --- native chunk walk: deal the pop order round-robin onto the
        # physical pool; each block walks its queue inside the kernel.  The
        # queue view has static shape, so this works under jit too (the
        # scalar-prefetch operands may be traced *values*).
        phys = min(NUM_QUEUES, nblk)
        cmax = -(-nblk // phys)
        rank = (np.arange(phys)[:, None]
                + np.arange(cmax)[None, :] * phys)          # [P, cmax]
        counts = jnp.asarray((rank < nblk).sum(1).astype(np.int32))
        chunks = pop_order[jnp.minimum(
            jnp.asarray(rank.reshape(-1), jnp.int32), nblk - 1)]
        out_padded = _kernel.segmented_matmul_chunked(
            lhs_padded, rhs, block_expert, chunks, counts,
            bm=bm, bn=bn, bk=bk, max_chunks=cmax)
    else:
        # --- pure/fallback: realize the queue as a host-side block
        # permutation feeding the plain kernel (one M-block per grid step).
        if schedule == "chunked_rr":
            lanes = min(NUM_QUEUES, nblk)
            perm = jnp.argsort(jnp.arange(nblk, dtype=jnp.int32) % lanes,
                               stable=True).astype(jnp.int32)
        else:
            perm = pop_order
        lhs_exec = lhs_padded.reshape(nblk, bm, k_dim)[perm].reshape(
            m_pad, k_dim)
        be_exec = block_expert[perm]
        out_exec = _kernel.segmented_matmul(lhs_exec, rhs, be_exec,
                                            bm=bm, bn=bn, bk=bk)
        # un-permute blocks, then unsort (gather each token's padded row)
        inv = jnp.zeros((nblk,), jnp.int32).at[perm].set(
            jnp.arange(nblk, dtype=jnp.int32))
        out_padded = out_exec.reshape(nblk, bm, -1)[inv].reshape(m_pad, -1)
    pos_orig = jnp.zeros((t_dim,), jnp.int32).at[order].set(pos_sorted)
    return out_padded[pos_orig]


def grouped_matmul(tokens: jax.Array, expert_of_token: jax.Array,
                   rhs: jax.Array, *, num_experts: int, bm: int = 128,
                   bn: int = 128, bk: int = 512,
                   schedule: str = "group_mapped",
                   execution_path: ExecutionPath | str = ExecutionPath.AUTO,
                   measure=None) -> jax.Array:
    """``out[t] = tokens[t] @ rhs[expert_of_token[t]]`` for ragged groups.

    ``tokens``: ``[T, K]``; ``expert_of_token``: int32 ``[T]`` in
    ``[0, num_experts)``; ``rhs``: ``[num_experts, K, N]``.  ``schedule``:
    one of ``SCHEDULE_POLICIES`` or ``"auto"``; ``execution_path``: native
    chunk-walking kernel vs permuted-grid fallback for the chunked policies
    (see module docstring).  ``measure`` is the measured-cost feedback knob
    for ``schedule="auto"`` (docs/autotune.md): ``None`` times candidates
    on this very GEMM when ``REPRO_AUTOTUNE_MEASURE=1``, ``False``
    disables, a callable ``(plan) -> median_us`` overrides.
    """
    if schedule == "auto":
        m = measure
        if m is None and not isinstance(expert_of_token, jax.core.Tracer):
            from repro.core.autotune import measurement_enabled
            if measurement_enabled():
                from repro.core.measure import time_fn

                def m(plan):
                    policy, p = plan_policy(plan)
                    f = functools.partial(
                        _grouped_matmul, num_experts=num_experts, bm=bm,
                        bn=bn, bk=bk, schedule=policy, path=p)
                    return time_fn(f, tokens, expert_of_token, rhs,
                                   warmup=1, iters=3)
        schedule = resolve_schedule(expert_of_token, num_experts,
                                    measure=None if m is False else m)
    # every policy has a device-side form: the plain scalar-prefetch kernel
    # for group_mapped (block == chunk), the chunk-walking kernel for the
    # chunked queues (which works under jit too — the queue view has static
    # shape).  "pure" forces the host-permuted fallback.
    path = resolve_execution_path(execution_path, native_supported=True)
    return _grouped_matmul(tokens, expert_of_token, rhs,
                           num_experts=num_experts, bm=bm, bn=bn, bk=bk,
                           schedule=schedule, path=str(path))
