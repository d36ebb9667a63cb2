"""Transformer building blocks: norms, RoPE, GQA attention, MLP variants.

Pure-function style: parameters are nested dicts of arrays, every block is
``apply(params, x, ...) -> y``.  Initializers return ``(params, specs)``
pairs where ``specs`` mirrors the param tree with ``PartitionSpec``s — the
distribution layer (``repro.train.step``) consumes them for FSDP x TP
sharding without the model code knowing about meshes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


Params = Dict[str, Any]

# Sharding axis names (see repro.launch.mesh): "data" = FSDP axis,
# "model" = tensor-parallel axis.  "pod" only shards the batch.
FSDP = "data"
TP = "model"
BATCH = ("pod", "data")


def maybe_constrain(x: jax.Array, *spec) -> jax.Array:
    """Sharding-constrain ``x`` against the ambient mesh (jax.set_mesh).

    No-op when no mesh is active (single-device tests).  Axis names absent
    from the ambient mesh are dropped, so the same annotations serve the
    (data, model) and (pod, data, model) production meshes.  These pins
    matter: GSPMD drops the batch sharding on mask/select chains built from
    iota (a measured 15x per-device blow-up of attention logits).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    cleaned = []
    for a in spec:
        if isinstance(a, tuple):
            keep = tuple(x_ for x_ in a if x_ in mesh.axis_names)
            cleaned.append(keep if keep else None)
        else:
            cleaned.append(a if a is None or a in mesh.axis_names else None)
    return jax.lax.with_sharding_constraint(x, P(*cleaned))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": P(None)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, hd]; positions: [B, S] (int)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                           # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (full / sliding-window, optional QKV bias, KV cache decode)
# ---------------------------------------------------------------------------

def _uniform(key, shape, scale, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def gather_in(w: jax.Array, dtype) -> jax.Array:
    """ZeRO-3 gather-at-use for a [in, out] matrix sharded P(FSDP, TP):
    all-gather the FSDP axis (in bf16) right before the matmul.  Without
    this pin GSPMD may instead partial-sum the *activations* over the data
    axis — measured 10 GiB/layer f32 all-reduces on danube prefill vs the
    ~0.04 GiB weight gather."""
    return maybe_constrain(w.astype(dtype), None, TP)


def gather_out(w: jax.Array, dtype) -> jax.Array:
    """Same for [in, out] matrices sharded P(TP, FSDP)."""
    return maybe_constrain(w.astype(dtype), TP, None)


def attention_init(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, qkv_bias: bool):
    ks = jax.random.split(key, 4)
    scale = (3.0 / d_model) ** 0.5
    params = {
        "wq": _uniform(ks[0], (d_model, num_heads * head_dim), scale),
        "wk": _uniform(ks[1], (d_model, num_kv_heads * head_dim), scale),
        "wv": _uniform(ks[2], (d_model, num_kv_heads * head_dim), scale),
        "wo": _uniform(ks[3], (num_heads * head_dim, d_model), scale),
    }
    specs = {
        "wq": P(FSDP, TP), "wk": P(FSDP, TP), "wv": P(FSDP, TP),
        "wo": P(TP, FSDP),
    }
    if qkv_bias:
        params.update({
            "bq": jnp.zeros((num_heads * head_dim,), jnp.float32),
            "bk": jnp.zeros((num_kv_heads * head_dim,), jnp.float32),
            "bv": jnp.zeros((num_kv_heads * head_dim,), jnp.float32),
        })
        specs.update({"bq": P(TP), "bk": P(TP), "bv": P(TP)})
    return params, specs


def _qkv(params: Params, x: jax.Array, num_heads: int, num_kv_heads: int,
         head_dim: int):
    b, s, _ = x.shape
    q = x @ gather_in(params["wq"], x.dtype)
    k = x @ gather_in(params["wk"], x.dtype)
    v = x @ gather_in(params["wv"], x.dtype)
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, num_heads, head_dim)
    k = k.reshape(b, s, num_kv_heads, head_dim)
    v = v.reshape(b, s, num_kv_heads, head_dim)
    return q, k, v


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, groups, axis=2)


def _attend(q, k, v, qpos, kpos, scale, sliding_window):
    """Masked softmax attention core. q:[B,Sq,H,hd], k/v:[B,Sk,H,hd]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = maybe_constrain(logits, BATCH, TP, None, None)
    i = qpos[:, None, :, None]
    j = kpos[:, None, None, :]
    mask = j <= i
    if sliding_window is not None:
        mask = jnp.logical_and(mask, j > i - sliding_window)
    logits = jnp.where(mask, logits, jnp.float32(-1e30))
    logits = maybe_constrain(logits, BATCH, TP, None, None)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(params: Params, x: jax.Array, positions: jax.Array, *,
              num_heads: int, num_kv_heads: int, head_dim: int,
              rope_theta: float, sliding_window: Optional[int] = None,
              query_chunk: Optional[int] = None, swa_banded: bool = False,
              unroll_chunks: bool = False, return_kv: bool = False):
    """Training/prefill causal self-attention. x: [B, S, D].

    ``query_chunk``: flash-style blocking — scores are materialized one
    ``[B, H, qc, S]`` block at a time under ``lax.scan`` instead of the full
    ``[B, H, S, S]``, bounding the transient memory at long context
    (the §Perf "chunked attention" lever).

    ``swa_banded`` (+``query_chunk`` +``sliding_window``): each query chunk
    attends only to its ``[chunk_start - window, chunk_end)`` KV band —
    compute AND memory drop from O(S^2) to O(S * (window + qc)), the banded
    sliding-window schedule (§Perf lever for the SWA archs).

    ``return_kv`` additionally returns the roped (k, v) for prefill cache
    emission.
    """
    b, s, d_model = x.shape
    q, k, v = _qkv(params, x, num_heads, num_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    groups = num_heads // num_kv_heads
    kk = _repeat_kv(k, groups)
    vv = _repeat_kv(v, groups)
    scale = head_dim ** -0.5

    banded = (swa_banded and sliding_window is not None
              and query_chunk is not None
              and s > query_chunk + sliding_window)
    if query_chunk is None or s <= query_chunk:
        out = _attend(q, kk, vv, positions, positions, scale, sliding_window)
    else:
        assert s % query_chunk == 0, (s, query_chunk)
        nq = s // query_chunk
        q_blocks = q.reshape(b, nq, query_chunk, num_heads, head_dim
                             ).swapaxes(0, 1)
        p_blocks = positions.reshape(b, nq, query_chunk).swapaxes(0, 1)

        if banded:
            band = query_chunk + sliding_window

            def blk(_, inp):
                qb, pb, i = inp
                start = jnp.clip(i * query_chunk - sliding_window, 0,
                                 s - band)
                kb = jax.lax.dynamic_slice_in_dim(kk, start, band, axis=1)
                vb = jax.lax.dynamic_slice_in_dim(vv, start, band, axis=1)
                pkb = jax.lax.dynamic_slice_in_dim(positions, start, band,
                                                   axis=1)
                return None, _attend(qb, kb, vb, pb, pkb, scale,
                                     sliding_window)

            xs = (q_blocks, p_blocks, jnp.arange(nq, dtype=jnp.int32))
        else:
            def blk(_, inp):
                qb, pb = inp
                return None, _attend(qb, kk, vv, pb, positions, scale,
                                     sliding_window)

            xs = (q_blocks, p_blocks)
        if unroll_chunks:  # roofline units: count every chunk's flops
            outs = [blk(None, jax.tree.map(lambda a: a[i], xs))[1]
                    for i in range(nq)]
            out_blocks = jnp.stack(outs)
        else:
            _, out_blocks = jax.lax.scan(blk, None, xs)
        out = out_blocks.swapaxes(0, 1).reshape(b, s, num_heads, head_dim)

    out = out.reshape(b, s, num_heads * head_dim) @ gather_out(
        params["wo"], x.dtype)
    if return_kv:
        return out, k, v
    return out


def attention_decode(params: Params, x: jax.Array, pos: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array, *,
                     num_heads: int, num_kv_heads: int, head_dim: int,
                     rope_theta: float, sliding_window: Optional[int] = None,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode step with a static-length KV cache.

    x: [B, 1, D]; pos: scalar int32 (current position, same for the batch);
    cache_k/v: [B, S_cache, Hkv, hd].  With ``sliding_window`` the cache is a
    ring buffer of length ``min(S_cache, window)`` indexed by ``pos % len``.
    Returns (out [B, 1, D], new_cache_k, new_cache_v).
    """
    b, _, _ = x.shape
    s_cache = cache_k.shape[1]
    q, k, v = _qkv(params, x, num_heads, num_kv_heads, head_dim)
    positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)

    # Ring-buffer slot; for full attention the caller sizes the cache to the
    # max sequence length so the ring never wraps.
    slot = pos % s_cache
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, slot, 0, 0))

    groups = num_heads // num_kv_heads
    kk = _repeat_kv(cache_k.astype(x.dtype), groups)   # [B, Sc, H, hd]
    vv = _repeat_kv(cache_v.astype(x.dtype), groups)

    scale = head_dim ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    # Validity: ring slot j holds absolute position p(j) = the largest
    # p <= pos with p % s_cache == j; valid iff p(j) >= 0 (written yet) and,
    # for SWA, p(j) > pos - window (always true when cache len == window).
    jslots = jnp.arange(s_cache, dtype=jnp.int32)
    wrap = (pos - jslots + s_cache) % s_cache
    abs_pos = pos - wrap
    valid = abs_pos >= 0
    logits = jnp.where(valid[None, None, None, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    out = out.reshape(b, 1, num_heads * head_dim) @ gather_out(
        params["wo"], x.dtype)
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, activation: str):
    ks = jax.random.split(key, 3)
    scale = (3.0 / d_model) ** 0.5
    if activation == "silu_glu":
        params = {"w1": _uniform(ks[0], (d_model, d_ff), scale),
                  "w3": _uniform(ks[1], (d_model, d_ff), scale),
                  "w2": _uniform(ks[2], (d_ff, d_model),
                                 (3.0 / d_ff) ** 0.5)}
        specs = {"w1": P(FSDP, TP), "w3": P(FSDP, TP), "w2": P(TP, FSDP)}
    else:  # non-gated (squared-relu / gelu)
        params = {"w1": _uniform(ks[0], (d_model, d_ff), scale),
                  "w2": _uniform(ks[2], (d_ff, d_model),
                                 (3.0 / d_ff) ** 0.5)}
        specs = {"w1": P(FSDP, TP), "w2": P(TP, FSDP)}
    return params, specs


def mlp(params: Params, x: jax.Array, activation: str) -> jax.Array:
    if activation == "silu_glu":
        h = jax.nn.silu(x @ gather_in(params["w1"], x.dtype)) * (
            x @ gather_in(params["w3"], x.dtype))
    elif activation == "sq_relu":
        h = jnp.square(jax.nn.relu(x @ gather_in(params["w1"], x.dtype)))
    elif activation == "gelu":
        h = jax.nn.gelu(x @ gather_in(params["w1"], x.dtype))
    else:
        raise ValueError(activation)
    return h @ gather_out(params["w2"], x.dtype)
