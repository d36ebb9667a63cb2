"""Jitted public wrapper: CSR in, dense y out, merge-path balanced."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.execute import ExecutionPath, choose_execution_path
from repro.core.schedules import Schedule
from repro.kernels.spmv_merge import kernel as _kernel
from repro.kernels.spmv_merge import ref as _ref

#: Grid the autotuner scores against when no explicit num_blocks is given
#: (matches the benchmark harness's processor count).
DEFAULT_NUM_BLOCKS = 64

#: Accepted ``schedule=`` spellings for the dynamic queue policies.
_CHUNK_POLICIES = {"chunked": "lpt", "chunked_lpt": "lpt",
                   "chunked_rr": "round_robin"}


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("num_rows", "nnz", "block_items"))
def _spmv_merge_path(row_offsets, col_indices, values, x, *, num_rows: int,
                     nnz: int, block_items: int):
    total = _round_up(max(num_rows + nnz, 1), block_items)
    stream_vals, stream_rows = _ref.merge_stream_ref(
        row_offsets, col_indices, values, x, num_rows, nnz, total)
    grid = total // block_items
    row_base = stream_rows[jnp.arange(grid, dtype=jnp.int32) * block_items]
    # A block may begin on padding (row == num_rows); clamp its base so the
    # one-hot window stays in range (its values are all zero regardless).
    row_base = jnp.minimum(row_base, max(num_rows - 1, 0))
    return _kernel.spmv_merge_stream(stream_vals, stream_rows, row_base,
                                     num_rows=num_rows,
                                     block_items=block_items)


def _spmv_measure(A, x, nb: int):
    """Measured-mode timing closure: one candidate plan on this very SpMV."""
    from repro.core.execute import execute_tile_reduce
    from repro.core.measure import time_fn
    from repro.core.schedules import make_partition
    spec = A.workspec()
    vals, cols = A.values, A.col_indices

    def run(plan) -> float:
        part = make_partition(spec, plan.schedule, nb)

        @jax.jit
        def f(xv):
            return execute_tile_reduce(spec, part,
                                       lambda nz: vals[nz] * xv[cols[nz]],
                                       path=plan.path)

        return time_fn(f, x, warmup=1, iters=3)
    return run


def spmv_merge_path(A, x, *, num_blocks: int | None = None,
                    block_items: int = 512,
                    schedule: Schedule | str | None = None,
                    execution_path: ExecutionPath | str = ExecutionPath.AUTO,
                    measure=None) -> jax.Array:
    """Merge-path SpMV ``y = A @ x`` for a :class:`repro.sparse.CSR` matrix.

    ``num_blocks`` (if given) overrides ``block_items`` to target a specific
    grid, mirroring the paper's processor-count parameterization.

    ``schedule`` (if given) sets the execution from a :class:`Partition`
    instead: ``"auto"`` asks the cost-model autotuner
    (:mod:`repro.core.autotune`) for a (schedule, path) plan; the dynamic
    spellings ``"chunked"``/``"chunked_lpt"``/``"chunked_rr"``/``"adaptive"``
    build the corresponding dynamic Partition and hand it to the
    :mod:`repro.core.execute` dispatcher.  With ``execution_path="auto"``
    (or ``"native"``) dynamic partitions run on the chunk-walking Pallas
    kernel — each physical block scalar-prefetches its chunk queue and walks
    it in-kernel; ``"pure"`` keeps the PR-1 fallbacks (chunk-granular merge
    stream for chunked, one merge stream per block otherwise).  Requires
    concrete (non-traced) ``A.row_offsets``.

    ``measure`` is the measured-cost feedback knob (docs/autotune.md):
    with ``schedule="auto"`` and ``REPRO_AUTOTUNE_MEASURE=1`` the
    autotuner times its top model-ranked candidates on *this* matrix and
    vector and re-ranks by measurement.  ``None`` builds the default
    timing closure when the env gate is on; ``False`` keeps selection
    model-only regardless; a callable ``(plan) -> median_us`` supplies
    custom timings.
    """
    num_rows = A.shape[0]
    if schedule is not None:
        policy = _CHUNK_POLICIES.get(str(schedule))
        sched = Schedule.CHUNKED if policy else Schedule(schedule)
        nb = num_blocks or DEFAULT_NUM_BLOCKS
        if sched == Schedule.AUTO:
            from repro.core.autotune import measurement_enabled, select_plan
            if callable(measure):
                m = measure
            elif measure is not False and measurement_enabled():
                m = _spmv_measure(A, x, nb)
            else:
                m = None
            plan = select_plan(A.workspec(), nb, measure=m)
            sched = plan.schedule
            policy = "lpt" if sched == Schedule.CHUNKED else None
            if ExecutionPath(execution_path) == ExecutionPath.AUTO:
                execution_path = plan.path
        if sched in (Schedule.CHUNKED, Schedule.ADAPTIVE):
            from repro.core.execute import execute_tile_reduce
            from repro.core.schedules import make_partition
            # an explicit "pure" request never consults the partition, so
            # skip the inspector (LPT assignment + queue inversion) entirely
            if ExecutionPath(execution_path) == ExecutionPath.PURE:
                path = ExecutionPath.PURE
            else:
                spec = A.workspec()
                part = make_partition(spec, sched, nb,
                                      chunk_policy=policy or "lpt")
                path = choose_execution_path(part, execution_path)
            if path == ExecutionPath.NATIVE:
                return execute_tile_reduce(
                    spec, part, A.values * x[A.col_indices], path=path)
            # pure fallback keeps PR-1 behavior: the kernel consumes a 1-D
            # merge stream; a chunked choice oversplits it into the
            # chunk-level grid (only the block granularity changes)
            if sched == Schedule.CHUNKED:
                from repro.core.dynamic import DEFAULT_CHUNK_FACTOR
                num_blocks = min(DEFAULT_CHUNK_FACTOR * nb, max(A.nnz, 1))
            else:
                num_blocks = nb
        else:
            num_blocks = nb
    if num_blocks is not None:
        block_items = max(_round_up(-(-(num_rows + A.nnz) // num_blocks), 128),
                          128)
    return _spmv_merge_path(A.row_offsets, A.col_indices, A.values, x,
                            num_rows=num_rows, nnz=A.nnz,
                            block_items=block_items)
