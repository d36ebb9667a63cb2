"""Production mesh definitions.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device;
only ``dryrun.py`` forces 512 host devices via XLA_FLAGS before any import.

Every mesh here has ``AxisType.Auto`` axes: ``jax.make_mesh`` now defaults
to ``Explicit`` axes, under which ``with_sharding_constraint`` asserts a
sharding instead of requesting it.

Axes:
* ``data`` — FSDP + batch data-parallel (16 chips: one v5e pod row)
* ``model`` — tensor/expert parallel (16 chips)
* ``pod`` — second data-parallel axis across pods (gradient all-reduce over
  DCN/ICI-over-pods); also the pipeline axis when PP is enabled.
"""
from __future__ import annotations

import jax
from jax import make_mesh
from jax.sharding import AxisType

__all__ = ["AxisType", "make_mesh", "make_production_mesh", "make_host_mesh",
           "make_graph_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Whatever devices exist locally, as (data, model) — for examples."""
    n = len(jax.devices())
    if model_axis <= 0 or n % model_axis != 0:
        raise ValueError(
            f"model_axis={model_axis} must evenly divide the local device "
            f"count ({n} available)")
    return make_mesh((n // model_axis, model_axis), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))


def make_graph_mesh(num_shards: int):
    """1-axis ``("shard",)`` mesh for sharded graph traversal.

    Used by :func:`repro.sparse.build_sharded_advance`; ``num_shards`` must
    not exceed the local device count (force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for CPU testing).
    """
    n = len(jax.devices())
    if num_shards <= 0 or num_shards > n:
        raise ValueError(
            f"num_shards={num_shards} must be in [1, {n}] "
            f"({n} local devices available)")
    return make_mesh((num_shards,), ("shard",),
                     axis_types=(AxisType.Auto,))
