"""Split-KV decode attention TPU kernel (FlashDecoding adapted to TPU).

FlashDecoding [arXiv:2311.01282] splits the KV sequence across SMs and
combines partial softmaxes.  On TPU the parallel unit is the grid program +
VMEM scratch, and the combine runs as a second tiny kernel — or, when the
cache's seq dim is sharded across chips, as a psum-based combine (the model
path in repro.models.transformer.decode_attend does exactly that through
GSPMD).  Here:

* grid = (batch*heads, n_splits); each program reduces its KV span to a
  partial (m, l, acc) triple written to HBM;
* ``combine_splits`` merges the triples exactly (log-sum-exp algebra) —
  associative, so the same code performs the cross-chip combine;
* KV tiles stream through VMEM in (block_k, d) chunks, d padded to 128
  lanes; cache_len masks the invalid tail.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

__all__ = ["decode_attention_kernel_call", "combine_splits"]

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, m_ref, l_ref, acc_ref, *,
                   block_k, split_len, sm_scale):
    si = pl.program_id(1)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (d,)
    cache_len = len_ref[0]

    n_blocks = split_len // block_k

    def body(kb, carry):
        m, l, acc = carry
        base = kb * block_k
        # slice-not-int leading index: see flash_attention kernel note
        k = pl.load(k_ref, (slice(0, 1), pl.dslice(base, block_k), slice(None)))[0]
        v = pl.load(v_ref, (slice(0, 1), pl.dslice(base, block_k), slice(None)))[0]
        s = jnp.dot(k.astype(jnp.float32), q)  # (block_k,)
        pos = si * split_len + base + jax.lax.iota(jnp.int32, block_k)
        s = jnp.where(pos < cache_len, s, NEG_INF)
        m_new = jnp.maximum(m, s.max())
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum()
        acc_new = acc * alpha + jnp.dot(p.astype(v.dtype), v).astype(jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.float32(NEG_INF), jnp.float32(0.0),
         jnp.zeros((q_ref.shape[-1],), jnp.float32)),
    )
    m_ref[0, 0] = m
    l_ref[0, 0] = l
    acc_ref[0, 0] = acc


def combine_splits(m, l, acc):
    """Exact LSE merge over the split axis (axis=-1 for m/l, -2 for acc).
    m, l: (..., n_splits); acc: (..., n_splits, d).  Returns (..., d)."""
    m_tot = m.max(axis=-1, keepdims=True)
    w = jnp.exp(m - m_tot)  # (..., s)
    l_tot = (l * w).sum(axis=-1)
    num = (acc * w[..., None]).sum(axis=-2)
    return num / jnp.maximum(l_tot, 1e-30)[..., None]


def decode_attention_kernel_call(
    q, k_cache, v_cache, cache_len, *, n_splits: int = 8, block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """q: (b, h, d); caches (b, S_max, h, d); cache_len scalar int32.
    Returns (b, h, d) in q.dtype."""
    b, h, d = q.shape
    smax = k_cache.shape[1]
    if smax % (n_splits * block_k):
        # shrink splits until they tile
        while n_splits > 1 and smax % (n_splits * block_k):
            n_splits //= 2
        if smax % (n_splits * block_k):
            block_k = smax // n_splits
    split_len = smax // n_splits

    qf = q.reshape(b * h, 1, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * h, smax, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * h, smax, d)
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b * h,))

    kernel = functools.partial(
        _decode_kernel, block_k=block_k, split_len=split_len,
        sm_scale=d ** -0.5,
    )
    m, l, acc = pl.pallas_call(
        kernel,
        grid=(b * h, n_splits),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bh, si: (bh, 0, 0)),
            pl.BlockSpec((1, split_len, d), lambda bh, si: (bh, si, 0)),
            pl.BlockSpec((1, split_len, d), lambda bh, si: (bh, si, 0)),
            pl.BlockSpec((1,), lambda bh, si: (bh,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda bh, si: (bh, si)),
            pl.BlockSpec((1, 1), lambda bh, si: (bh, si)),
            pl.BlockSpec((1, 1, d), lambda bh, si: (bh, si, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, n_splits), jnp.float32),
            jax.ShapeDtypeStruct((b * h, n_splits), jnp.float32),
            jax.ShapeDtypeStruct((b * h, n_splits, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf, lens)
    out = combine_splits(m, l, acc)  # (b*h, d)
    return out.reshape(b, h, d).astype(q.dtype)
