"""Flash attention TPU kernel (pl.pallas_call + BlockSpec VMEM tiling).

TPU adaptation of FlashAttention [arXiv:2205.14135] (a CUDA-SRAM algorithm):
instead of warp-level tiling we tile for the MXU/VMEM hierarchy —

* grid = (batch*heads, q_blocks); each program owns a (BLOCK_Q, head_dim)
  query tile resident in VMEM and streams KV tiles HBM->VMEM via the
  BlockSpec index_map (no manual DMA needed at this level);
* the online-softmax state (m, l, acc) lives in VMEM scratch across the
  innermost fori_loop over KV blocks;
* BLOCK sizes are multiples of 128 to keep the MXU systolic array full
  (lane dim) and the fp32 accumulators aligned to (8,128) vregs;
* causal masking skips fully-masked KV blocks by clamping the loop bound
  (block-level early exit — the TPU analogue of CUDA's per-warp skip).

Validated in interpret mode on CPU against ref.py (tests/test_kernels.py);
the model's XLA path (repro.models.layers.gqa_attend) is the lowering twin
used by the dry-run.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

__all__ = ["flash_attention_kernel_call"]

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k, seq_kv,
                 causal, q_offset, sm_scale):
    qi = pl.program_id(1)  # query-block index
    q = q_ref[0].astype(jnp.float32) * sm_scale  # (block_q, d)

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)

    n_kv_blocks = seq_kv // block_k
    if causal:
        # last kv block that intersects this q block's causal frontier
        hi = jax.lax.min(
            n_kv_blocks,
            (qi * block_q + block_q - 1 + q_offset) // block_k + 1,
        )
    else:
        hi = n_kv_blocks

    def body(kb, carry):
        m, l, acc = carry
        # leading index must be a slice: interpret-mode discharge rejects
        # bare python ints (jax<=0.4.x), so load (1, bk, d) and squeeze
        k = pl.load(k_ref, (slice(0, 1), pl.dslice(kb * block_k, block_k), slice(None)))[0]
        v = pl.load(v_ref, (slice(0, 1), pl.dslice(kb * block_k, block_k), slice(None)))[0]
        s = jnp.dot(q, k.astype(jnp.float32).T)  # (bq, bk) fp32 on MXU
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + q_offset
            kpos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v
        ).astype(jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel_call(
    q, k, v, *, causal: bool = True, q_offset: int = 0,
    block_q: int = 128, block_k: int = 128, interpret: Optional[bool] = None,
):
    """q: (b, sq, h, d); k, v: (b, skv, h, d) (GQA pre-expanded).

    Layout: fold (b, h) into the grid's first axis; per program the q tile is
    (block_q, d) and the full per-(b,h) KV stream is visible to pl.load via a
    (skv, d) block (the compiler pipelines the dslice loads HBM->VMEM).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if sq % block_q or skv % block_k:
        raise ValueError(f"seq ({sq},{skv}) must tile by ({block_q},{block_k})")
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, skv, d)

    kernel = functools.partial(
        _attn_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_kv=skv,
        causal=causal,
        q_offset=q_offset,
        sm_scale=d ** -0.5,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, skv, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, skv, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
