"""Public wrappers: GQA-aware banded SWA flash attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_swa import kernel as _kernel


def flash_swa(q, k, v, *, window: int, qc: int = 256) -> jax.Array:
    return _kernel.flash_swa(q, k, v, window=window, qc=qc)


def flash_swa_gqa(q, k, v, *, window: int, qc: int = 256) -> jax.Array:
    """GQA: q [B,S,H,hd], k/v [B,S,Hkv,hd] with H % Hkv == 0.  The repeat is
    a broadcast-reshape (no copy under XLA) before the kernel."""
    h, hkv = q.shape[2], k.shape[2]
    groups = h // hkv
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    return flash_swa(q, k, v, window=window, qc=qc)
