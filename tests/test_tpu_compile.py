"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel at the size a real
deployment hands it and compiles it with the TPU compiler for a ``v5e:2x2``
topology that is described, not attached.  That is where Mosaic refuses
unaligned slices, unsupported gathers and VMEM overflows that the Pallas
interpreter accepts.  Each compile must produce a Mosaic custom call: a
kernel that was interpreted instead would compile to plain HLO and pass
silently otherwise.

Shapes follow a Graph500 scale-20 graph (2**20 vertices, 2**25 directed
edges) split into 16384 chunks over 4096 queues, and the OLMoE expert layer
(8192 routed rows, d_model 2048, d_ff 1024, 64 experts, bf16).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and every
test worker imports this file.  The tests skip only where the TPU compiler
(the ``libtpu`` package) is not installed, as in CI; any other failure to
describe the topology fails them.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import (ExecutionPath, Partition, Schedule, WorkSpec,
                        compact_rungs, estimate_compact_capacity)
from repro.core.execute import (native_chunk_tile_reduce,
                                native_chunk_value_windows,
                                native_compact_value_windows)
from repro.kernels.segmm.kernel import segmented_matmul
from repro.kernels.spmv_merge.kernel import spmv_merge_stream
from repro.serve.graph import GraphServer, QueryBatch
from repro.sparse import CSR, Graph
from repro.sparse.advance import AdvancePlan

V, A = 1 << 20, 1 << 25          # scale-20 vertices, directed edges
C, P, MAX_CHUNKS = 16384, 4096, 8
WINDOW, TILE_SPAN = 4096, 2048
LANES = 8                         # GraphServer lanes
HBM_BYTES = 16 * 10 ** 9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.fixture(scope="module")
def graph_shapes(one_chip):
    """(spec, part) of a scale-20 advance, as shapes on one described chip."""
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    spec = WorkSpec(tile_offsets=sds((V + 1,)), num_atoms=A, num_tiles=V)
    part = Partition(schedule=Schedule.CHUNKED, num_blocks=C,
                     items_per_block=WINDOW, atom_starts=sds((C + 1,)),
                     tile_starts=sds((C + 1,)), tile_aligned=False,
                     block_map=sds((C,)), num_physical_blocks=P,
                     atom_span=WINDOW, tile_span=TILE_SPAN,
                     block_chunks=sds((P, MAX_CHUNKS)),
                     block_chunk_counts=sds((P,)))
    return spec, part, sds


@pytest.mark.parametrize("combiner", ["sum", "min"])
def test_chunk_walk_tiles(graph_shapes, combiner):
    spec, part, sds = graph_shapes

    def pull(spec, part, vals, mask):
        return native_chunk_tile_reduce(spec, part, lambda e: vals[e],
                                        combiner=combiner, atom_mask=mask)

    _compile(pull, spec, part, sds((A,), jnp.float32), sds((A,), jnp.bool_))


def test_chunk_walk_atoms(graph_shapes):
    spec, part, sds = graph_shapes

    def push(spec, part, vals, mask):
        return native_chunk_value_windows(spec, part, lambda e: vals[e],
                                          combiner="min", atom_mask=mask)

    _compile(push, spec, part, sds((A,), jnp.float32), sds((A,), jnp.bool_))


def test_chunk_walk_atoms_vmapped_over_lanes(graph_shapes):
    spec, part, sds = graph_shapes

    def push(spec, part, vals, mask):
        return jax.vmap(lambda v, m: native_chunk_value_windows(
            spec, part, lambda e: v[e], combiner="min", atom_mask=m))(
                vals, mask)

    _compile(push, spec, part, sds((LANES, A), jnp.float32),
             sds((LANES, A), jnp.bool_))


#: The push plan's compaction ladder: 15 rungs from 15,728,640 slots down
#: to 960, each walked as one chunk per 1,024 slots (at most ``C``), one
#: chunk per grid step.
RUNGS = compact_rungs(estimate_compact_capacity(A, 0.375))


def test_chunk_walk_compact(graph_shapes):
    """The top rung: 15,360 chunks."""
    test_chunk_walk_compact_rungs(graph_shapes, 0)


@pytest.mark.parametrize("rung", [len(RUNGS) // 2, len(RUNGS) - 1],
                         ids=["middle", "bottom"])
def test_chunk_walk_compact_rungs(graph_shapes, rung):
    """A middle rung (122,880 slots, 120 chunks) and the bottom one (960
    slots, one chunk)."""
    spec, part, sds = graph_shapes

    def push(spec, part, vals, idx):
        return native_compact_value_windows(spec, part, lambda e: vals[e],
                                            idx, combiner="min")

    _compile(push, spec, part, sds((A,), jnp.float32),
             sds((RUNGS[rung],), jnp.int32))


def test_graph_server_step_fits_one_chip(graph_shapes):
    """The 8-lane serving step at scale 20 fits one chip's HBM.

    Lanes lead every per-edge array: vmapped the plain way, its gathers
    alone asked for over 30 GB.
    """
    _check_server_step_fits(graph_shapes, "pull")


def test_graph_server_push_step_fits_one_chip(graph_shapes):
    """The push-direction serving step, whose lanes share one compaction
    rung (the largest lane's), fits one chip's HBM too."""
    _check_server_step_fits(graph_shapes, "push")


def _check_server_step_fits(graph_shapes, direction):
    spec, part, sds = graph_shapes
    plan = AdvancePlan(
        spec=spec, src=sds((A,)), weight=sds((A,), jnp.float32), part=part,
        schedule=Schedule.CHUNKED, path=ExecutionPath.NATIVE,
        push_spec=spec, dst=sds((A,)), push_weight=sds((A,), jnp.float32),
        push_src=sds((A,)), push_part=part, push_schedule=Schedule.CHUNKED,
        push_path=ExecutionPath.NATIVE, num_vertices=V,
        out_degrees=sds((V,)), direction_threshold=0.375,
        compact_capacity=A // 2)
    lane = lambda dtype=jnp.int32: sds((LANES,), dtype)
    row = lambda dtype: sds((LANES, V), dtype)
    batch = QueryBatch(kind=lane(), source=lane(), qid=lane(),
                       active=lane(jnp.bool_), done=lane(jnp.bool_),
                       iters=lane(), value=row(jnp.float32),
                       frontier=row(jnp.bool_), active_edges=lane(),
                       delta=lane(jnp.float32), pushes=lane())
    # the step takes its sizes from the batch; a two-vertex server makes it
    tiny = Graph(CSR(jnp.array([0, 1, 2]), jnp.array([1, 0]), jnp.ones(2),
                     (2, 2), 2))
    step = GraphServer(tiny, lanes=LANES, direction=direction)._make_step()
    memory = _compile(step, plan, batch).memory_analysis()
    assert (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            < HBM_BYTES)


def test_spmv_merge_stream(one_chip):
    block_items = 512
    grid = (V + A) // block_items

    def spmv(vals, rows, row_base):
        return spmv_merge_stream(vals, rows, row_base, num_rows=V,
                                 block_items=block_items)

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    _compile(spmv, sds((grid * block_items,), jnp.float32),
             sds((grid * block_items,), jnp.int32), sds((grid,), jnp.int32))


def test_segmented_matmul_olmoe(one_chip):
    m, k, n, experts, bm = 8192, 2048, 1024, 64, 128

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    _compile(lambda lhs, rhs, be: segmented_matmul(lhs, rhs, be, bm=bm),
             sds((m, k), jnp.bfloat16), sds((experts, k, n), jnp.bfloat16),
             sds((m // bm,), jnp.int32))
