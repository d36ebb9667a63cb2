"""Native chunk-walking execution path (repro.core.execute + Pallas kernels).

The acceptance bar for the device-side dynamic-schedule path: the native
Pallas chunk-walking kernels must be *bit-identical* to the pure-JAX blocked
executor and to the reference implementations, for every schedule, every
combiner, including empty chunks and ``num_chunks < num_blocks``.  Workload
zoo, oracles and comparators live in the shared conformance library
(``tests/_conformance.py``); this file owns the native-path-specific
routing/fallback/queue-inversion checks.
"""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    ExecutionPath, Plan, Schedule, WorkSpec, blocked_tile_reduce,
    choose_execution_path, execute_tile_reduce, invert_block_map,
    make_partition, native_chunk_tile_reduce, resolve_execution_path,
    score_plans, select_plan, supports_native_execution, tile_reduce,
)
from _conformance import (
    COMBINERS, WORKLOADS, assert_bitwise_equal,
    check_tile_reduce_conformance, int_valued_atom_fn, np_tile_reduce,
    int_valued_atom_values, spec_from_sizes,
)

SCHEDULES = [Schedule.CHUNKED, Schedule.ADAPTIVE, Schedule.NONZERO_SPLIT,
             Schedule.MERGE_PATH, Schedule.THREAD_MAPPED]


class TestNativeTileReduce:
    @pytest.mark.parametrize("schedule", SCHEDULES)
    @pytest.mark.parametrize("name", sorted(WORKLOADS))
    def test_bit_identical_to_pure_and_oracle(self, schedule, name):
        spec = spec_from_sizes(WORKLOADS[name])
        part = make_partition(spec, schedule, 4)
        fn = int_valued_atom_fn(spec)
        native = native_chunk_tile_reduce(spec, part, fn)
        pure = blocked_tile_reduce(spec, part, fn)
        oracle = tile_reduce(spec, fn)
        assert_bitwise_equal(native, pure, f"{schedule}/{name} vs pure")
        assert_bitwise_equal(native, oracle, f"{schedule}/{name} vs oracle")

    @pytest.mark.parametrize("combiner", COMBINERS)
    @pytest.mark.parametrize("name", sorted(WORKLOADS))
    def test_combiner_matrix_matches_numpy_oracle(self, name, combiner):
        # the full schedule x path matrix per combiner, differenced against
        # the pure-NumPy oracle (no jax on the reference side)
        spec = spec_from_sizes(WORKLOADS[name])
        vals = int_valued_atom_values(spec.num_atoms, seed=3)
        jvals = jnp.asarray(vals)
        fn = lambda a: jvals[jnp.minimum(a, max(spec.num_atoms - 1, 0))]
        oracle = np_tile_reduce(np.asarray(spec.tile_offsets), vals, combiner)
        check_tile_reduce_conformance(spec, fn, combiner=combiner,
                                      oracle=oracle)

    @pytest.mark.parametrize("combiner", COMBINERS)
    def test_atom_mask_matrix_matches_numpy_oracle(self, combiner):
        # the frontier-mask operand: masked atoms contribute the identity
        # on every schedule x path, bit-identically to NumPy
        spec = spec_from_sizes(WORKLOADS["powerlaw"])
        vals = int_valued_atom_values(spec.num_atoms, seed=5)
        mask = np.random.default_rng(6).random(spec.num_atoms) < 0.4
        jvals, jmask = jnp.asarray(vals), jnp.asarray(mask)
        fn = lambda a: jvals[jnp.minimum(a, max(spec.num_atoms - 1, 0))]
        oracle = np_tile_reduce(np.asarray(spec.tile_offsets), vals,
                                combiner, mask)
        check_tile_reduce_conformance(spec, fn, combiner=combiner,
                                      atom_mask=jmask, oracle=oracle)

    @pytest.mark.parametrize("schedule",
                             [Schedule.CHUNKED, Schedule.ADAPTIVE])
    def test_fewer_chunks_than_blocks(self, schedule):
        # num_atoms=2 caps the chunked oversplit at 2 chunks for 8 blocks;
        # most physical blocks then own an empty queue.
        spec = spec_from_sizes([0, 1, 0, 1, 0])
        part = make_partition(spec, schedule, 8)
        fn = int_valued_atom_fn(spec)
        assert_bitwise_equal(native_chunk_tile_reduce(spec, part, fn),
                             tile_reduce(spec, fn))

    def test_empty_chunks(self):
        # all-empty tiles inside the span produce zero-atom chunks
        spec = spec_from_sizes([4, 0, 0, 0, 0, 4])
        part = make_partition(spec, Schedule.CHUNKED, 4)
        fn = int_valued_atom_fn(spec)
        assert_bitwise_equal(native_chunk_tile_reduce(spec, part, fn),
                             tile_reduce(spec, fn))

    def test_all_empty_workload(self):
        spec = spec_from_sizes([0, 0, 0])
        part = make_partition(spec, Schedule.CHUNKED, 4)
        out = native_chunk_tile_reduce(spec, part, lambda a: a * 0.0)
        np.testing.assert_array_equal(np.asarray(out), np.zeros(3, np.float32))

    def test_dispatcher_routes_dynamic_to_native(self):
        spec = spec_from_sizes(WORKLOADS["powerlaw"])
        part = make_partition(spec, Schedule.CHUNKED, 4)
        assert choose_execution_path(part) == ExecutionPath.NATIVE
        fn = int_valued_atom_fn(spec)
        assert_bitwise_equal(execute_tile_reduce(spec, part, fn),
                             tile_reduce(spec, fn))

    def test_dispatcher_dtype_fallback(self):
        # the native kernel accumulates in f32: auto must fall back to
        # pure for other dtypes (not raise), and accept f32 spellings
        spec = spec_from_sizes(WORKLOADS["powerlaw"])
        part = make_partition(spec, Schedule.CHUNKED, 4)
        fn = int_valued_atom_fn(spec)
        got = execute_tile_reduce(spec, part, fn, dtype=jnp.bfloat16)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(tile_reduce(spec, fn, dtype=jnp.bfloat16),
                       np.float32), rtol=0.05, atol=0.5)
        assert_bitwise_equal(
            execute_tile_reduce(spec, part, fn, dtype="float32"),
            tile_reduce(spec, fn))
        with pytest.raises(ValueError):
            execute_tile_reduce(spec, part, fn, dtype=jnp.bfloat16,
                                path="native")

    def test_dispatcher_pure_fallback_under_tracing(self):
        # a partition built inside jit has traced boundaries and no span
        # hints: auto must fall back to pure, native must raise
        spec = spec_from_sizes(WORKLOADS["uniform"])
        fn = int_valued_atom_fn(spec)

        def traced(offsets):
            s = WorkSpec.from_segment_offsets(offsets,
                                              num_atoms=spec.num_atoms,
                                              num_tiles=spec.num_tiles)
            p = make_partition(s, Schedule.NONZERO_SPLIT, 4)
            assert not supports_native_execution(p)
            assert choose_execution_path(p) == ExecutionPath.PURE
            with pytest.raises(ValueError):
                resolve_execution_path("native", native_supported=False)
            return execute_tile_reduce(s, p, fn)

        got = jax.jit(traced)(spec.tile_offsets)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(tile_reduce(spec, fn)))


class TestLaneTake:
    """``lane_take`` is ``x[idx]``, lanes leading, however it is vmapped."""

    x = jnp.arange(4 * 50, dtype=jnp.float32).reshape(4, 50) * 0.5
    idx = jnp.asarray([[3, 0, 49], [7, 7, 1]], jnp.int32)

    @pytest.mark.parametrize("batched", ["x", "idx", "both"])
    def test_matches_indexing_under_vmap(self, batched):
        from repro.core.execute import lane_take
        x, idx = self.x, self.idx
        if batched == "idx":
            x, idx = x[0], jnp.stack([idx, idx[::-1], idx + 1, idx + 2])
        elif batched == "both":
            idx = jnp.stack([idx, idx[::-1], idx + 1, idx + 2])
        axes = {"x": (0, None), "idx": (None, 0), "both": (0, 0)}[batched]
        got = jax.jit(jax.vmap(lane_take, in_axes=axes))(x, idx)
        want = jax.vmap(lambda a, i: a[i], in_axes=axes)(x, idx)
        assert got.shape == want.shape
        assert_bitwise_equal(got, want)

    def test_nested_vmap_and_unbatched(self):
        from repro.core.execute import lane_take
        xs = jnp.stack([self.x, -self.x])                    # [2, 4, 50]
        got = jax.vmap(jax.vmap(lane_take, in_axes=(0, None)),
                       in_axes=(0, None))(xs, self.idx)
        assert_bitwise_equal(got, xs[:, :, self.idx])
        assert_bitwise_equal(lane_take(self.x[1], self.idx),
                             self.x[1][self.idx])


class TestInvertBlockMap:
    def test_round_trip(self):
        bm = jnp.asarray([2, 0, 1, 0, 2, 2], jnp.int32)
        chunks, counts = invert_block_map(bm, 3)
        assert chunks.shape == (3, 3)
        np.testing.assert_array_equal(np.asarray(counts), [2, 1, 3])
        np.testing.assert_array_equal(np.asarray(chunks[0, :2]), [1, 3])
        np.testing.assert_array_equal(np.asarray(chunks[1, :1]), [2])
        np.testing.assert_array_equal(np.asarray(chunks[2, :3]), [0, 4, 5])

    def test_built_once_on_partition(self):
        spec = spec_from_sizes(WORKLOADS["powerlaw"])
        part = make_partition(spec, Schedule.CHUNKED, 4)
        assert part.block_chunks is not None
        assert part.block_chunk_counts is not None
        assert int(part.block_chunk_counts.sum()) == part.num_blocks
        # every chunk appears exactly once across the queues
        seen = []
        bc = np.asarray(part.block_chunks)
        for p, n in enumerate(np.asarray(part.block_chunk_counts)):
            seen.extend(bc[p, :n].tolist())
        assert sorted(seen) == list(range(part.num_blocks))


class TestSegmmNativePath:
    def _setup(self, seed=0, T=96, K=32, N=16, E=5):
        rng = np.random.default_rng(seed)
        tokens = jnp.asarray(rng.integers(-3, 4, (T, K)).astype(np.float32))
        rhs = jnp.asarray(rng.integers(-3, 4, (E, K, N)).astype(np.float32))
        eot = jnp.asarray(rng.integers(0, E, T).astype(np.int32))
        return tokens, eot, rhs, E

    @pytest.mark.parametrize("sched", ["chunked_rr", "chunked_lpt"])
    def test_native_bit_identical_to_pure_and_static(self, sched):
        from repro.kernels.segmm import ops as segmm_ops
        from repro.kernels.segmm import ref as segmm_ref
        tokens, eot, rhs, E = self._setup()
        base = segmm_ops.grouped_matmul(tokens, eot, rhs, num_experts=E,
                                        bm=16, schedule="group_mapped")
        native = segmm_ops.grouped_matmul(tokens, eot, rhs, num_experts=E,
                                          bm=16, schedule=sched,
                                          execution_path="native")
        pure = segmm_ops.grouped_matmul(tokens, eot, rhs, num_experts=E,
                                        bm=16, schedule=sched,
                                        execution_path="pure")
        assert_bitwise_equal(native, pure)
        assert_bitwise_equal(native, base)
        np.testing.assert_allclose(
            np.asarray(native),
            np.asarray(segmm_ref.grouped_matmul_ref(tokens, eot, rhs)),
            rtol=1e-6)

    def test_native_under_jit(self):
        from repro.kernels.segmm import ops as segmm_ops
        tokens, eot, rhs, E = self._setup(seed=1)
        f = jax.jit(lambda t, e, r: segmm_ops.grouped_matmul(
            t, e, r, num_experts=E, bm=16, schedule="chunked_lpt",
            execution_path="native"))
        base = segmm_ops.grouped_matmul(tokens, eot, rhs, num_experts=E,
                                        bm=16, schedule="group_mapped")
        assert_bitwise_equal(f(tokens, eot, rhs), base)


class TestSpmvNativePath:
    def _matrix(self, seed=0, rows=48, cols=32):
        from repro.sparse.formats import CSR
        rng = np.random.default_rng(seed)
        dens = np.round(rng.random((rows, cols)) * 8)
        dens *= rng.random((rows, cols)) < 0.15
        dens[rows // 2] = np.round(rng.random(cols) * 8)   # heavy row
        A = CSR.from_dense(jnp.asarray(dens.astype(np.float32)))
        x = jnp.asarray(rng.integers(-4, 5, cols).astype(np.float32))
        return A, x, dens

    @pytest.mark.parametrize("sched", ["chunked_lpt", "chunked_rr",
                                       "adaptive"])
    def test_native_matches_executor_and_reference(self, sched):
        from repro.core.dynamic import adaptive_partition, chunked_partition
        from repro.kernels.spmv_merge import ops as spmv_ops
        A, x, dens = self._matrix()
        got = spmv_ops.spmv_merge_path(A, x, schedule=sched, num_blocks=8)
        want = dens @ np.asarray(x)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
        spec = A.workspec()
        if sched == "adaptive":
            part = adaptive_partition(spec, 8)
        else:
            policy = "lpt" if sched == "chunked_lpt" else "round_robin"
            part = chunked_partition(spec, 8, policy=policy)
        vals, cols_ = A.values, A.col_indices
        atom_fn = lambda nz: vals[nz] * x[cols_[nz]]
        assert_bitwise_equal(got, blocked_tile_reduce(spec, part, atom_fn))

    def test_pure_fallback_matches(self):
        from repro.kernels.spmv_merge import ops as spmv_ops
        A, x, dens = self._matrix(seed=2)
        got = spmv_ops.spmv_merge_path(A, x, schedule="chunked_lpt",
                                       num_blocks=8, execution_path="pure")
        np.testing.assert_allclose(np.asarray(got), dens @ np.asarray(x),
                                   rtol=1e-6)


class TestPlanSelection:
    def test_select_plan_is_argmin(self):
        for sizes in WORKLOADS.values():
            spec = spec_from_sizes(sizes)
            plan = select_plan(spec, 16, cache=None)
            scores = score_plans(spec, 16)
            assert scores[plan] == min(scores.values())

    def test_native_chunked_outranks_pure_chunked(self):
        rng = np.random.default_rng(0)
        sizes = (rng.pareto(0.8, 500) * 20 + 1).astype(np.int64)
        spec = spec_from_sizes(sizes)
        scores = score_plans(spec, 64)
        native = Plan(Schedule.CHUNKED, ExecutionPath.NATIVE)
        pure = Plan(Schedule.CHUNKED, ExecutionPath.PURE)
        assert scores[native] < scores[pure]
        assert select_plan(spec, 64, cache=None) == native

    def test_auto_partition_supports_native(self):
        # acceptance: make_partition(spec, "auto", nb) can select the
        # native path — the partition it returns must be consumable by the
        # native executor whenever a dynamic schedule wins
        rng = np.random.default_rng(0)
        sizes = (rng.pareto(0.8, 500) * 20 + 1).astype(np.int64)
        spec = spec_from_sizes(sizes)
        part = make_partition(spec, "auto", 64)
        assert supports_native_execution(part)
        fn = int_valued_atom_fn(spec)
        assert_bitwise_equal(execute_tile_reduce(spec, part, fn),
                             tile_reduce(spec, fn))

    def test_plan_cache_roundtrip_and_legacy_values(self, tmp_path):
        from repro.core import AutotuneCache
        path = tmp_path / "cache.json"
        cache = AutotuneCache(path)
        spec = spec_from_sizes(WORKLOADS["powerlaw"])
        plan = select_plan(spec, 16, cache=cache)
        reloaded = AutotuneCache(path)
        assert select_plan(spec, 16, cache=reloaded) == plan
        # PR-1 files store bare schedule names: decoded as pure-path plans
        path.write_text(json.dumps({"legacy": "merge_path"}))
        fresh = AutotuneCache(path)
        assert fresh.get_plan("legacy") == Plan(Schedule.MERGE_PATH,
                                                ExecutionPath.PURE)
        assert fresh.get("legacy") == Schedule.MERGE_PATH
