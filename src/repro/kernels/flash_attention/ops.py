"""jit'd public wrapper for the flash-attention kernel.

Shape policy: pads seq to the block multiple, expands GQA KV heads, picks
block sizes by sequence length, and dispatches kernel vs oracle by
``impl`` ('pallas' | 'xla').  ``interpret`` defaults to the platform:
the interpreter on a CPU backend, the compiled kernel on TPU (the
BlockSpecs are already MXU/VMEM-aligned).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel_call
from repro.kernels.flash_attention.ref import flash_attention_ref

__all__ = ["flash_attention"]


def _expand_kv(k, n_heads):
    kv = k.shape[2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=2)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "impl", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True, q_offset: int = 0, impl: str = "pallas",
    block_q: int = 128, block_k: int = 128, interpret: Optional[bool] = None,
):
    """q: (b, sq, H, d); k, v: (b, skv, KV, d) with H % KV == 0."""
    b, sq, h, d = q.shape
    kf = _expand_kv(k, h)
    vf = _expand_kv(v, h)
    if impl == "xla":
        return flash_attention_ref(q, kf, vf, causal=causal, q_offset=q_offset)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(kf.shape[1], 8))
    pad_q = (-sq) % bq
    pad_k = (-kf.shape[1]) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        # padded KV columns must never win the softmax: with causal masking
        # they are masked whenever q_offset keeps qpos < kpos; for the
        # non-causal case mask via a -inf K contribution is required — we
        # simply require no K padding for non-causal calls.
        if not causal:
            raise ValueError("non-causal calls require skv % block_k == 0")
    out = flash_attention_kernel_call(
        q, kf, vf, causal=causal, q_offset=q_offset,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    return out[:, :sq]
