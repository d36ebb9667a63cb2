"""Pipeline parallelism over the pod axis (GPipe schedule).

At multi-pod scale the cross-pod links are the slowest tier, so instead of
data-parallel gradient sync over ``pod`` the framework can run the layer
stack as P pipeline stages (one per pod): activations stream stage-to-stage
over point-to-point ``ppermute`` (cheap on the pod interconnect), and only
microbatch activations — never weights or gradients — cross pods.

Schedule: classic GPipe.  T = num_micro + P - 1 ticks; at tick ``t`` stage
``s`` computes microbatch ``t - s`` (bubble ticks compute masked garbage —
the standard utilization cost ``(P-1)/T``).  All stages run one SPMD program
under ``shard_map``; the inter-stage hop is a single ``ppermute``.  The
whole schedule is differentiable (``ppermute`` transposes to the reverse
permute), so ``jax.grad`` through it yields pipeline-parallel training
without a hand-written backward schedule.

Stage weights live only on their pod (``P('pod', ...)`` on the stacked
stage dim) — pipeline parallelism is also the memory play that lets a
340B-class model drop the FSDP all-gather traffic entirely.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


AXIS = "pod"


def split_stages(layer_params, num_stages: int):
    """Reshape stacked layer params [L, ...] -> [P, L//P, ...]."""
    def r(x):
        l = x.shape[0]
        assert l % num_stages == 0, (l, num_stages)
        return x.reshape((num_stages, l // num_stages) + x.shape[1:])
    return jax.tree.map(r, layer_params)


def make_pipeline_apply(stage_fn: Callable, mesh: Mesh, num_stages: int,
                        num_micro: int):
    """Build ``apply(stage_params, xs) -> ys``.

    ``stage_fn(params_stage, x) -> y`` applies one stage's layers to one
    microbatch activation ``[mb, ...]``.  ``xs``: ``[num_micro, mb, ...]``
    microbatched inputs (replicated across pods); returns ``ys`` of the same
    shape from the last stage.
    """
    assert num_micro >= 1 and num_stages >= 1
    ticks = num_micro + num_stages - 1
    perm = [(i, i + 1) for i in range(num_stages - 1)]

    def per_pod(stage_params, xs):
        # shapes inside shard_map: stage_params [1, L/P, ...]; xs full
        # (replicated).  Drop the leading stage dim.
        stage_params_local = jax.tree.map(lambda p: p[0], stage_params)
        stage = jax.lax.axis_index(AXIS)
        mb_shape = xs.shape[1:]

        def tick(act_in, t):
            mb_idx = jnp.clip(t - stage, 0, num_micro - 1)
            x_t = jnp.where(stage == 0, xs[jnp.clip(t, 0, num_micro - 1)],
                            act_in)
            y = stage_fn(stage_params_local, x_t)
            act_next = jax.lax.ppermute(y, AXIS, perm) if perm else y
            return act_next, y

        act0 = jnp.zeros(mb_shape, xs.dtype)
        _, ys = jax.lax.scan(tick, act0, jnp.arange(ticks))
        # keep only this stage's outputs; callers read the last stage's.
        return ys[None]  # [1, T, mb, ...] -> stacked over pods by out_spec

    sharded = jax.shard_map(
        per_pod, mesh=mesh,
        in_specs=(P(AXIS), P()),        # stage params by pod; inputs repl.
        out_specs=P(AXIS),              # [P, T, mb, ...]
        check_vma=False)

    def apply(stage_params, xs):
        ys_all = sharded(stage_params, xs)                  # [P, T, mb, ...]
        # valid outputs of the LAST stage are ticks P-1 .. P-1+num_micro
        return ys_all[num_stages - 1, num_stages - 1:
                      num_stages - 1 + num_micro]
    return apply


def reference_apply(stage_fn, stage_params, xs, num_stages: int):
    """Sequential oracle: run every stage in order on each microbatch."""
    def one_micro(x):
        for s in range(num_stages):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x
    return jax.vmap(one_micro)(xs)
