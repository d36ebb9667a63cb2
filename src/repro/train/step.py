"""Distributed train step: FSDP x TP sharding, microbatch accumulation, remat.

``make_train_step`` builds a jitted step:

* **params/optimizer sharding**: from the model's partition specs — matrices
  FSDP-sharded over ``data`` and TP-sharded over ``model`` (GSPMD inserts the
  per-layer weight all-gathers and gradient reduce-scatters; with a ``pod``
  axis the gradient reduction becomes hierarchical automatically).
* **microbatching**: ``lax.scan`` over ``num_microbatches`` slices with fp32
  grad accumulation — this is what fits 340B training activations in 16 GB
  chips (saved activations scale with the microbatch, not the global batch).
* **remat**: per-layer ``jax.checkpoint`` inside the model (cfg.remat).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import init_params, lm_loss
from repro.train.optimizer import OptConfig, adamw_update

BATCH_AXES = ("pod", "data")  # batch shards over every data-parallel axis


def batch_pspec(mesh: Mesh) -> P:
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    if not axes:
        return P()
    return P(axes if len(axes) > 1 else axes[0])


def param_specs(cfg: ModelConfig):
    """Partition specs without materializing full-size params.

    Specs depend only on the *structure* of the param tree (family, bias
    flags, expert counts), never on dimensions — so they are built from the
    reduced structural twin, which is cheap to init for any config.
    """
    _, specs = init_params(cfg.reduced(), jax.random.PRNGKey(0))
    return specs


def shardings_for(mesh: Mesh, specs) -> Any:
    """PartitionSpec tree -> NamedSharding tree, dropping axes the mesh does
    not have (so the same specs serve single- and multi-pod meshes)."""
    def fix(spec: P) -> NamedSharding:
        cleaned = []
        for a in spec:
            if a is None:
                cleaned.append(None)
            elif isinstance(a, tuple):
                keep = tuple(x for x in a if x in mesh.axis_names)
                cleaned.append(keep if keep else None)
            else:
                cleaned.append(a if a in mesh.axis_names else None)
        return NamedSharding(mesh, P(*cleaned))
    return jax.tree.map(fix, specs, is_leaf=lambda x: isinstance(x, P))


def opt_shardings(mesh: Mesh, param_sh) -> Dict[str, Any]:
    return {"mu": param_sh, "nu": param_sh,
            "step": NamedSharding(mesh, P())}


def _constrain_batch(batch, mesh: Optional[Mesh]):
    """Re-pin the batch dim sharding — GSPMD loses it after the microbatch
    reshape/slice, which would leave attention logits batch-replicated
    (a ~15x per-device memory blowup measured on qwen train_4k)."""
    if mesh is None:
        return batch
    spec = batch_pspec(mesh)
    if spec == P():
        return batch

    def pin(x):
        full = P(*(tuple(spec) + (None,) * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, full))
    return jax.tree.map(pin, batch)


def loss_and_grads(params, cfg: ModelConfig, batch, num_microbatches: int,
                   dtype=jnp.bfloat16, mesh: Optional[Mesh] = None):
    """Grad accumulation over microbatches via lax.scan."""
    if num_microbatches <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            lm_loss, has_aux=True)(params, cfg, _constrain_batch(batch, mesh),
                                   dtype=dtype)
        return loss, {"loss": metrics["loss"],
                      "ntokens": metrics["ntokens"]}, grads

    def reshape(x):
        b = x.shape[0]
        assert b % num_microbatches == 0, (b, num_microbatches)
        return x.reshape((num_microbatches, b // num_microbatches)
                         + x.shape[1:])

    micro = jax.tree.map(reshape, batch)
    acc_dtype = jnp.bfloat16 if cfg.grad_accum_bf16 else jnp.float32
    zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype),
                              params)

    def body(carry, mb):
        acc, loss_acc, ntok = carry
        mb = _constrain_batch(mb, mesh)
        (loss, metrics), grads = jax.value_and_grad(
            lm_loss, has_aux=True)(params, cfg, mb, dtype=dtype)
        acc = jax.tree.map(lambda a, g: a + g.astype(acc_dtype), acc, grads)
        return (acc, loss_acc + loss, ntok + metrics["ntokens"]), None

    (grads, loss_sum, ntok), _ = jax.lax.scan(
        body, (zero_grads, jnp.zeros((), jnp.float32),
               jnp.zeros((), jnp.float32)), micro)
    inv = 1.0 / num_microbatches
    grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
    return loss_sum * inv, {"loss": loss_sum * inv, "ntokens": ntok}, grads


METRIC_KEYS = ("loss", "ntokens", "grad_norm", "lr")


def make_train_step(cfg: ModelConfig, opt_cfg: OptConfig, mesh: Mesh, *,
                    num_microbatches: int = 1, dtype=jnp.bfloat16,
                    grad_compress: Optional[Callable] = None):
    """Returns (jitted_step, param_shardings, opt_shardings).

    ``jitted_step(params, opt_state, batch) -> (params, opt_state, metrics)``.
    ``grad_compress`` (see repro.train.compress) is applied to accumulated
    grads before the optimizer — int8 error-feedback cross-pod reduction.
    """
    specs = param_specs(cfg)
    param_sh = shardings_for(mesh, specs)
    opt_sh = opt_shardings(mesh, param_sh)
    scalar_sh = NamedSharding(mesh, P())

    def step_fn(params, opt_state, batch):
        # the abstract mesh is active while this traces -> maybe_constrain
        # pins activation shardings against it.
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            loss, metrics, grads = loss_and_grads(params, cfg, batch,
                                                  num_microbatches, dtype,
                                                  mesh=mesh)
            if grad_compress is not None:
                grads = grad_compress(grads)
            new_params, new_opt, opt_metrics = adamw_update(params, grads,
                                                            opt_state,
                                                            opt_cfg)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    step = jax.jit(
        step_fn,
        in_shardings=(param_sh, opt_sh, None),
        out_shardings=(param_sh, opt_sh,
                       {k: scalar_sh for k in METRIC_KEYS}),
        donate_argnums=(0, 1))
    return step, param_sh, opt_sh
