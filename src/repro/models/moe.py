"""Mixture-of-Experts with load-balanced dispatch.

Routing is the canonical irregular workload inside an LM: after top-k, the
(token, expert) pairs are **atoms** and experts are **tiles** of wildly
different sizes.  Two dispatch executors, same router:

* ``dispatch="capacity"`` — dense one-hot/einsum dispatch with a capacity
  factor (Shazeer-style).  Fully static, shards over the mesh (experts on the
  TP axis -> GSPMD emits the expert-parallel all_to_all).  This is the path
  the multi-pod dry-run lowers.
* ``dispatch="sorted"`` — the paper's schedule: sort atoms by tile, pad
  groups to M-blocks, run the balanced Pallas segmented GEMM
  (:mod:`repro.kernels.segmm`).  No token dropping, perfectly balanced
  blocks; validated against the capacity path at capacity -> inf.

Aux losses: standard load-balancing loss (mean gate fraction x mean route
fraction) + router z-loss.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import (BATCH, FSDP, TP, _uniform, gather_in,
                                 gather_out, maybe_constrain)

Params = Dict[str, Any]

# Expert-parallel axis: experts live on the TP axis of the mesh.
EP = TP


def moe_init(key, d_model: int, d_ff: int, num_experts: int,
             num_shared: int, activation: str):
    ks = jax.random.split(key, 7)
    scale = (3.0 / d_model) ** 0.5
    fscale = (3.0 / d_ff) ** 0.5
    params: Params = {
        "router": _uniform(ks[0], (d_model, num_experts), scale),
        "w1": _uniform(ks[1], (num_experts, d_model, d_ff), scale),
        "w3": _uniform(ks[2], (num_experts, d_model, d_ff), scale),
        "w2": _uniform(ks[3], (num_experts, d_ff, d_model), fscale),
    }
    specs = {
        "router": P(None, None),
        "w1": P(EP, FSDP, None), "w3": P(EP, FSDP, None),
        "w2": P(EP, None, FSDP),
    }
    if num_shared > 0:
        params.update({
            "sw1": _uniform(ks[4], (d_model, num_shared * d_ff), scale),
            "sw3": _uniform(ks[5], (d_model, num_shared * d_ff), scale),
            "sw2": _uniform(ks[6], (num_shared * d_ff, d_model), fscale),
        })
        specs.update({"sw1": P(FSDP, TP), "sw3": P(FSDP, TP),
                      "sw2": P(TP, FSDP)})
    del activation  # experts are silu_glu in both assigned MoE archs
    return params, specs


def _router(params: Params, x2d: jax.Array, num_experts: int, top_k: int):
    """Returns (topk_idx [T,k], topk_w [T,k], aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, top_k)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)

    # load-balance loss (Switch): E * sum_e f_e * p_e
    route_frac = jnp.mean(
        jax.nn.one_hot(topk_idx, num_experts, dtype=jnp.float32), axis=(0, 1))
    gate_frac = jnp.mean(probs, axis=0)
    lb_loss = num_experts * jnp.sum(route_frac * gate_frac)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return topk_idx, topk_w, lb_loss + 1e-3 * z_loss


def _expert_ffn(w1, w3, w2, h):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def moe_capacity_einsum(params: Params, x: jax.Array, *, num_experts: int,
                        top_k: int, capacity_factor: float = 1.25,
                        ) -> Tuple[jax.Array, jax.Array]:
    """Dense one-hot/einsum dispatch (Shazeer-style reference).

    O(T * E * C) memory — only viable at smoke scale; kept as the executable
    specification that the production sort-based dispatch is tested against.
    """
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    topk_idx, topk_w, aux = _router(params, x2d, num_experts, top_k)

    capacity = max(int(capacity_factor * t * top_k / num_experts), 1)
    # position of each (token, k) atom within its expert's queue
    onehot = jax.nn.one_hot(topk_idx, num_experts, dtype=jnp.int32)  # [T,k,E]
    flat = onehot.reshape(t * top_k, num_experts)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(
        t, top_k, num_experts)
    within = pos_in_expert < capacity
    # dispatch tensor [T, E, C] (bool -> dtype); combine with router weights
    pos_oh = jax.nn.one_hot(jnp.sum(pos_in_expert * onehot, -1), capacity,
                            dtype=x.dtype)                     # [T, k, C]
    keep = (jnp.sum(onehot * within, -1) > 0).astype(x.dtype)  # [T, k]
    disp = jnp.einsum("tke,tkc,tk->tec", onehot.astype(x.dtype), pos_oh, keep)
    comb = jnp.einsum("tke,tkc,tk,tk->tec", onehot.astype(x.dtype), pos_oh,
                      keep, topk_w.astype(x.dtype))

    xe = jnp.einsum("td,tec->ecd", x2d, disp)                  # [E, C, D]
    he = jax.vmap(_expert_ffn)(params["w1"].astype(x.dtype),
                               params["w3"].astype(x.dtype),
                               params["w2"].astype(x.dtype), xe)
    out = jnp.einsum("ecd,tec->td", he, comb)
    return out.reshape(b, s, d), aux


def moe_capacity(params: Params, x: jax.Array, *, num_experts: int,
                 top_k: int, capacity_factor: float = 1.25,
                 ep_pins: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Sort-based capacity dispatch — the production/distributed path.

    The paper's schedule vocabulary at chip granularity: atoms = routed
    (token, k) pairs, tiles = experts.  Atoms are *sorted by tile* (one
    argsort), each atom's rank within its tile computed from the tile
    offsets (group-mapped prefix-sum binning), then scattered into the
    static ``[E, C, D]`` expert buffer; rank >= C drops (capacity).  Memory
    is O(T*D + E*C*D) — no [T, E, C] one-hot — and with experts sharded over
    the ``model`` axis GSPMD turns the scatter/gather into the
    expert-parallel all_to_all.
    """
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    topk_idx, topk_w, aux = _router(params, x2d, num_experts, top_k)
    capacity = max(int(capacity_factor * t * top_k / num_experts), 1)

    ta = t * top_k
    atom_expert = topk_idx.reshape(ta)
    atom_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    atom_w = topk_w.reshape(ta)

    order = jnp.argsort(atom_expert)                    # sort atoms by tile
    sizes = jnp.bincount(atom_expert, length=num_experts)
    offsets = jnp.concatenate([jnp.zeros((1,), sizes.dtype),
                               jnp.cumsum(sizes)])
    sorted_e = atom_expert[order]
    rank = jnp.arange(ta, dtype=jnp.int32) - offsets[sorted_e].astype(
        jnp.int32)                                       # rank within expert
    kept = rank < capacity
    slot = jnp.where(kept, sorted_e * capacity + rank, num_experts * capacity)

    xe_flat = jnp.zeros((num_experts * capacity + 1, d), x.dtype)
    xe_flat = xe_flat.at[slot].set(x2d[atom_token[order]], mode="drop")
    xe = xe_flat[:-1].reshape(num_experts, capacity, d)
    if ep_pins:
        # pin the expert buffer to the EP axis (measured on the 16x16 mesh:
        # REGRESSION — GSPMD replicates the scatter source; kept switchable,
        # see EXPERIMENTS.md §Perf cell B iteration log)
        xe = maybe_constrain(xe, EP, None, None)

    he = jax.vmap(_expert_ffn)(params["w1"].astype(x.dtype),
                               params["w3"].astype(x.dtype),
                               params["w2"].astype(x.dtype), xe)
    if ep_pins:
        he = maybe_constrain(he, EP, None, None)

    he_flat = jnp.concatenate(
        [he.reshape(num_experts * capacity, d),
         jnp.zeros((1, d), he.dtype)], axis=0)
    out_atoms = he_flat[slot] * (atom_w[order] * kept)[:, None].astype(
        he.dtype)
    out = jax.ops.segment_sum(out_atoms, atom_token[order], num_segments=t)
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_sorted(params: Params, x: jax.Array, *, num_experts: int, top_k: int,
               bm: int = 128, schedule: str = "group_mapped",
               execution_path: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """The paper's load-balanced dispatch: sort atoms by tile, pad to
    M-blocks, balanced segmented GEMM.  Drop-free.

    ``schedule``: segmm block-order policy (``"group_mapped"``,
    ``"chunked_rr"``, ``"chunked_lpt"``) or ``"auto"`` — the cost-model
    autotuner inspects the concrete routing (atoms = routed pairs, tiles =
    experts) and picks; under jit the routing is traced, so ``"auto"``
    resolves to the static default (see ``repro.kernels.segmm.ops``).
    ``execution_path`` routes the chunked policies through the
    :mod:`repro.core.execute` dispatcher: ``"native"``/``"auto"`` walk the
    expert M-blocks inside the chunk-walking Pallas kernel, ``"pure"``
    keeps the host-permuted fallback.
    """
    from repro.kernels.segmm import ops as segmm_ops

    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    topk_idx, topk_w, aux = _router(params, x2d, num_experts, top_k)

    # atoms = (token, k) pairs
    atom_expert = topk_idx.reshape(t * top_k).astype(jnp.int32)
    atom_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    atoms_in = x2d[atom_token]                              # [T*k, D]

    if schedule == "auto":
        # one inspection serves all three GEMMs (same routing).  Measured
        # mode (REPRO_AUTOTUNE_MEASURE=1, docs/autotune.md) times the
        # candidate policies on the first GEMM's actual operands — the
        # other two share its routing, so one measured record covers all.
        measure = None
        if not isinstance(atom_expert, jax.core.Tracer):
            from repro.core.autotune import measurement_enabled
            if measurement_enabled():
                import functools

                from repro.core.measure import time_fn

                def measure(plan):
                    policy, p = segmm_ops.plan_policy(plan)
                    f = functools.partial(
                        segmm_ops.grouped_matmul, num_experts=num_experts,
                        bm=bm, schedule=policy, execution_path=p)
                    return time_fn(f, atoms_in, atom_expert, params["w1"],
                                   warmup=1, iters=3)
        schedule = segmm_ops.resolve_schedule(atom_expert, num_experts,
                                              measure=measure)

    h1 = segmm_ops.grouped_matmul(atoms_in, atom_expert, params["w1"],
                                  num_experts=num_experts, bm=bm,
                                  schedule=schedule,
                                  execution_path=execution_path)
    h3 = segmm_ops.grouped_matmul(atoms_in, atom_expert, params["w3"],
                                  num_experts=num_experts, bm=bm,
                                  schedule=schedule,
                                  execution_path=execution_path)
    h = jax.nn.silu(h1) * h3
    out_atoms = segmm_ops.grouped_matmul(h.astype(x.dtype), atom_expert,
                                         params["w2"],
                                         num_experts=num_experts, bm=bm,
                                         schedule=schedule,
                                         execution_path=execution_path)
    weighted = out_atoms * topk_w.reshape(t * top_k, 1)
    out = jax.ops.segment_sum(weighted, atom_token, num_segments=t)
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_capacity_grouped(params: Params, x: jax.Array, *, num_experts: int,
                         top_k: int, capacity_factor: float = 1.25,
                         ) -> Tuple[jax.Array, jax.Array]:
    """Per-row (grouped) capacity dispatch — the distributed-scale schedule.

    The flat sort-based dispatch sorts ALL tokens globally; under GSPMD a
    batch-sharded global argsort becomes a distributed sort (measured:
    192 GiB/device of collective-permute traffic on olmoe train_4k).  The
    paper's locality lesson at chip granularity: partition the atoms by
    *row* (tiles = experts per row), sort each row locally — the sorts are
    vmapped over the batch dim, which is batch-sharded, so they never cross
    a chip — and let only the routed activations move when the expert einsum
    contracts against the expert-sharded weights.  Capacity is per row
    (ceil(cf * S * k / E)); drop-free at cf -> inf like the flat version.
    """
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    topk_idx, topk_w, aux = _router(params, x2d, num_experts, top_k)
    capacity = max(int(capacity_factor * s * top_k / num_experts), 1)

    sk = s * top_k
    atom_expert = topk_idx.reshape(b, sk)
    atom_w = topk_w.reshape(b, sk)
    atom_token = jnp.repeat(jnp.arange(s, dtype=jnp.int32), top_k)  # per row

    order = jnp.argsort(atom_expert, axis=1)               # local, vmapped
    sorted_e = jnp.take_along_axis(atom_expert, order, axis=1)
    sizes = jax.vmap(lambda e: jnp.bincount(e, length=num_experts)
                     )(atom_expert)                         # [B, E]
    offsets = jnp.concatenate(
        [jnp.zeros((b, 1), sizes.dtype), jnp.cumsum(sizes, axis=1)], axis=1)
    rank = (jnp.arange(sk, dtype=jnp.int32)[None]
            - jnp.take_along_axis(offsets, sorted_e, axis=1).astype(
                jnp.int32))
    kept = rank < capacity
    slot = jnp.where(kept, sorted_e * capacity + rank,
                     num_experts * capacity)                # [B, Sk]

    x3d = x2d.reshape(b, s, d)
    tok_sorted = jnp.take_along_axis(
        jnp.broadcast_to(atom_token[None], (b, sk)), order, axis=1)
    gathered = jnp.take_along_axis(x3d, tok_sorted[..., None],
                                   axis=1)                  # [B, Sk, D]

    def scatter_row(slots, vals):
        buf = jnp.zeros((num_experts * capacity + 1, d), vals.dtype)
        return buf.at[slots].set(vals, mode="drop")

    xe = jax.vmap(scatter_row)(slot, gathered)[:, :-1].reshape(
        b, num_experts, capacity, d)                        # [B, E, C, D]
    xe = maybe_constrain(xe, BATCH, EP, None, None)

    w1 = params["w1"].astype(x.dtype)
    w3 = params["w3"].astype(x.dtype)
    w2 = params["w2"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w1)) * jnp.einsum(
        "becd,edf->becf", xe, w3)
    he = jnp.einsum("becf,efd->becd", h, w2)                # [B, E, C, D]
    he = maybe_constrain(he, BATCH, EP, None, None)

    he_flat = jnp.concatenate(
        [he.reshape(b, num_experts * capacity, d),
         jnp.zeros((b, 1, d), he.dtype)], axis=1)
    out_atoms = jnp.take_along_axis(he_flat, slot[..., None], axis=1)
    w_sorted = jnp.take_along_axis(atom_w, order, axis=1)
    out_atoms = out_atoms * (w_sorted * kept)[..., None].astype(he.dtype)
    out = jax.vmap(lambda v, t: jax.ops.segment_sum(v, t, num_segments=s)
                   )(out_atoms, tok_sorted)
    return out.astype(x.dtype), aux


def moe_shared(params: Params, x: jax.Array) -> jax.Array:
    """Shared experts (DeepSeekMoE): a dense gated MLP every token visits."""
    h = jax.nn.silu(x @ gather_in(params["sw1"], x.dtype)) * (
        x @ gather_in(params["sw3"], x.dtype))
    return h @ gather_out(params["sw2"], x.dtype)


def moe(params: Params, x: jax.Array, *, num_experts: int, top_k: int,
        num_shared: int, dispatch: str = "capacity",
        capacity_factor: float = 1.25, schedule: str = "group_mapped",
        execution_path: str = "auto",
        ep_pins: bool = False) -> Tuple[jax.Array, jax.Array]:
    if dispatch == "capacity":
        out, aux = moe_capacity(params, x, num_experts=num_experts,
                                top_k=top_k, capacity_factor=capacity_factor,
                                ep_pins=ep_pins)
    elif dispatch == "grouped":
        out, aux = moe_capacity_grouped(params, x, num_experts=num_experts,
                                        top_k=top_k,
                                        capacity_factor=capacity_factor)
    elif dispatch == "sorted":
        out, aux = moe_sorted(params, x, num_experts=num_experts,
                              top_k=top_k, schedule=schedule,
                              execution_path=execution_path)
    else:
        raise ValueError(dispatch)
    if num_shared > 0:
        out = out + moe_shared(params, x)
    return out, aux
