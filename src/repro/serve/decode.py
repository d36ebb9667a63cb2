"""Serving: jitted one-token decode step with sharded KV caches + sampling.

Decode sharding policy (see DESIGN.md):

* batch over the data-parallel axes when divisible (decode_32k: B=128 over
  16 data shards);
* KV/state *sequence* axis over the model axis — essential when
  ``kv_heads < model_axis`` (glm4-9b has 2 KV heads on a 16-wide TP axis).
  Softmax over a sequence-sharded axis makes GSPMD emit the partial-max /
  partial-sum reductions — the flash-decode combine — on its own;
* long_500k (B=1): batch replicated, cache sharded over ``model`` only.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import cache_shape, decode_step


def _data_axes(mesh: Mesh, batch: int):
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if axes and batch % size == 0 and batch >= size:
        return tuple(axes)
    return ()


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, batch: int
                 ) -> Dict[str, P]:
    """Partition specs per cache leaf: [L, B, S, Hkv, hd] etc."""
    daxes = _data_axes(mesh, batch)
    b_ax = daxes if daxes else None
    tp = "model" if "model" in mesh.axis_names else None
    specs: Dict[str, P] = {}
    if cfg.family in ("dense", "vlm", "audio", "moe", "hybrid"):
        # prefer sharding KV heads over TP (local ring updates); fall back
        # to the sequence axis when kv_heads < TP width (e.g. glm4's kv=2)
        tp_width = mesh.shape.get("model", 1) if tp else 1
        if tp and cfg.num_kv_heads % tp_width == 0:
            specs["k"] = P(None, b_ax, None, tp, None)
            specs["v"] = P(None, b_ax, None, tp, None)
        else:
            specs["k"] = P(None, b_ax, tp, None, None)
            specs["v"] = P(None, b_ax, tp, None, None)
    if cfg.family == "ssm":
        # [L, B, H, K, V]: H (e.g. 40) need not divide TP; shard K instead
        specs["wkv"] = P(None, b_ax, None, tp, None)
        specs["xprev_t"] = P(None, b_ax, None, None)
        specs["xprev_c"] = P(None, b_ax, None, None)
    if cfg.family == "hybrid":
        specs["h"] = P(None, b_ax, tp, None)             # d_inner over TP
    return specs


def make_serve_step(cfg: ModelConfig, mesh: Mesh, *, batch: int,
                    seq_len: int, dtype=jnp.bfloat16):
    """Returns (jitted_step, param_sh, cache_sh, input_sds).

    ``jitted_step(params, tokens [B,1], pos, cache) -> (logits, new_cache)``
    with the cache donated (in-place ring update on device).
    """
    from repro.train.step import param_specs, shardings_for

    param_sh = shardings_for(mesh, param_specs(cfg))
    cache_sh = shardings_for(mesh, cache_pspecs(cfg, mesh, batch))
    daxes = _data_axes(mesh, batch)
    tok_sh = NamedSharding(mesh, P(daxes if daxes else None, None))

    def step_fn(params, tokens, pos, cache):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return decode_step(params, cfg, tokens, pos, cache, dtype=dtype)

    step = jax.jit(step_fn,
                   in_shardings=(param_sh, tok_sh, None, cache_sh),
                   out_shardings=(None, cache_sh),
                   donate_argnums=(3,))
    cache_sds = jax.tree.map(
        lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        cache_shape(cfg, batch, seq_len, dtype), cache_sh)
    tok_sds = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=tok_sh)
    return step, param_sh, cache_sh, {"tokens": tok_sds, "cache": cache_sds}


def sample_logits(key, logits: jax.Array, temperature: float = 1.0,
                  vocab_size: int | None = None) -> jax.Array:
    """Greedy (T=0) or temperature sampling. logits: [B, 1, V] -> [B, 1].

    ``vocab_size`` masks the vocab-padding columns (``padded_vocab`` rounds
    the head up to a lane multiple) to ``-inf`` so neither argmax nor
    categorical can ever emit an out-of-vocab token id.
    """
    last = logits[:, -1]
    if vocab_size is not None and vocab_size < last.shape[-1]:
        keep = jnp.arange(last.shape[-1]) < vocab_size
        last = jnp.where(keep, last, jnp.float32(-jnp.inf))
    if temperature == 0.0:
        return jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
    return jax.random.categorical(
        key, last / temperature, axis=-1)[:, None].astype(jnp.int32)


def generate(params, cfg: ModelConfig, prompt: jax.Array, *, steps: int,
             cache, key=None, temperature: float = 0.0,
             dtype=jnp.float32) -> Tuple[jax.Array, Any]:
    """Simple autoregressive loop (prefill via repeated decode) for tests
    and the serving example; production uses make_serve_step."""
    b, plen = prompt.shape
    key = key if key is not None else jax.random.PRNGKey(0)
    out = []
    tok = prompt[:, :1]
    for t in range(plen + steps - 1):
        logits, cache = decode_step(params, cfg, tok, jnp.int32(t), cache,
                                    dtype=dtype)
        if t + 1 < plen:
            tok = prompt[:, t + 1:t + 2]
        else:
            key, sub = jax.random.split(key)
            tok = sample_logits(sub, logits, temperature,
                                vocab_size=cfg.vocab_size)
            out.append(tok)
    if not out:  # steps == 0: nothing sampled, [B, 0] keeps callers total
        return jnp.zeros((b, 0), jnp.int32), cache
    return jnp.concatenate(out, axis=1), cache
