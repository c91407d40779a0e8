"""Chunked SSD scan TPU kernel (pl.pallas_call + BlockSpec VMEM tiling).

TPU adaptation of the Mamba-2 SSD algorithm [arXiv:2405.21060] (originally a
CUDA kernel family): one grid program per (batch, head); the chunk loop runs
INSIDE the kernel as a fori_loop carrying the (N, P) state in VMEM scratch —
the HBM round-trip of the inter-chunk state pass (separate kernels on GPU)
disappears because VMEM persists across the sequential grid walk.

Per chunk (length CL, all in VMEM):
  decay cumsums   (CL,)     vector unit
  G = C @ B^T     (CL, CL)  MXU
  masked weights  (CL, CL)  vector unit
  y_intra = (G*W) @ (x*dt)  MXU
  state update    S = d*S + B^T @ (x*w)   MXU, stays in scratch

Block sizes: CL fixed at 128 (mask/cumsum tiles align to the 8x128 vreg),
P and N up to 128 each (head_dim 64 and state 64/128 in our archs).
Validated in interpret mode against ref.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

__all__ = ["ssd_scan_kernel_call"]


def _ssd_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, dskip_ref, y_ref,
                st_ref, *, chunk, n_chunks):
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))  # scalar A < 0 (this head)
    d_skip = dskip_ref[0].astype(jnp.float32)
    n = b_ref.shape[-1]
    p = x_ref.shape[-1]

    def body(ci, state):
        sl = pl.dslice(ci * chunk, chunk)
        # slice-not-int leading index: see flash_attention kernel note
        x = pl.load(x_ref, (slice(0, 1), sl, slice(None)))[0].astype(jnp.float32)  # (CL,P)
        dt = pl.load(dt_ref, (slice(0, 1), sl))[0].astype(jnp.float32)  # (CL,)
        bm = pl.load(b_ref, (slice(0, 1), sl, slice(None)))[0].astype(jnp.float32)  # (CL,N)
        cm = pl.load(c_ref, (slice(0, 1), sl, slice(None)))[0].astype(jnp.float32)

        la = dt * a  # (CL,) log decays
        cum = jnp.cumsum(la)  # inclusive
        total = cum[-1]

        g = jnp.dot(cm, bm.T)  # (CL, CL) MXU
        ldiff = cum[:, None] - cum[None, :]
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        mask = row >= col
        w = jnp.where(mask, g * jnp.exp(jnp.where(mask, ldiff, 0.0)), 0.0)
        xdt = x * dt[:, None]
        y = jnp.dot(w, xdt)  # (CL, P) intra-chunk

        # inter-chunk: y += exp(cum) * (C @ S_prev)
        y = y + jnp.exp(cum)[:, None] * jnp.dot(cm, state)
        y = y + d_skip * x
        pl.store(y_ref, (slice(0, 1), sl, slice(None)), y[None].astype(y_ref.dtype))

        # state update: S = exp(total) * S + B^T @ (x * exp(total-cum) * dt)
        win = (jnp.exp(total - cum) * dt)[:, None] * x  # (CL,P)
        state = jnp.exp(total) * state + jnp.dot(bm.T, win)  # (N,P)
        return state

    state = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros((n, p), jnp.float32)
    )
    st_ref[0] = state.astype(st_ref.dtype)


def ssd_scan_kernel_call(x, dt, a_log, b, c, d_skip, *, chunk: int = 128,
                         interpret: Optional[bool] = None):
    """x (B,S,H,P); dt (B,S,H); a_log (H,); b,c (B,S,G,N); d_skip (H,).
    Returns (y (B,S,H,P), final_state (B,H,N,P))."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk} != 0")
    rep = h // g
    # flatten (B, H) into the grid; expand B/C groups to heads
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, s)
    bf = jnp.repeat(b, rep, axis=2).transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
    cf = jnp.repeat(c, rep, axis=2).transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
    alog_t = jnp.tile(a_log, bsz)  # (B*H,)
    dskip_t = jnp.tile(d_skip, bsz)

    kernel = functools.partial(
        _ssd_kernel, chunk=chunk, n_chunks=s // chunk
    )
    y, st = pl.pallas_call(
        kernel,
        grid=(bsz * h,),
        in_specs=[
            pl.BlockSpec((1, s, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
            pl.BlockSpec((1, s, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, p), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz * h, n, p), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xf, dtf, alog_t, bf, cf, dskip_t)
    y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
    st = st.reshape(bsz, h, n, p)
    return y, st
