"""jit'd public wrapper for split-KV decode attention."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_kernel_call
from repro.kernels.decode_attention.ref import decode_attention_ref

__all__ = ["decode_attention"]


@functools.partial(
    jax.jit, static_argnames=("impl", "n_splits", "block_k", "interpret")
)
def decode_attention(q, k_cache, v_cache, cache_len, *, impl: str = "pallas",
                     n_splits: int = 8, block_k: int = 128,
                     interpret: Optional[bool] = None):
    """q: (b, h, d); caches (b, S_max, KV, d), H % KV == 0."""
    b, h, d = q.shape
    kv = k_cache.shape[2]
    if kv != h:
        k_cache = jnp.repeat(k_cache, h // kv, axis=2)
        v_cache = jnp.repeat(v_cache, h // kv, axis=2)
    if impl == "xla":
        return decode_attention_ref(q, k_cache, v_cache, cache_len)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    return decode_attention_kernel_call(
        q, k_cache, v_cache, cache_len, n_splits=n_splits, block_k=block_k,
        interpret=interpret,
    )
