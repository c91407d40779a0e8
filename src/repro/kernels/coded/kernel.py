"""Pallas/JAX combine kernel shared by coded encode and decode.

Both ends of a coded job are the SAME linear map — encode multiplies an
``(n, k)`` coefficient matrix into the k data blocks, decode multiplies a
``(k', m)`` weight matrix into the m surviving responses — so one kernel
body serves both.  The Pallas variant holds the whole coefficient matrix
and block stack as one VMEM block (a coded job's matrices are a few rows
by at most a few thousand columns) and contracts them in one ``jnp.dot``
at full f32 precision, so decode weights from an ill-conditioned
Vandermonde inverse keep their accuracy on the MXU.  It compiles for TPU;
``interpret`` defaults to the platform (interpreter on CPU only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..platform import resolve_interpret


def _combine_fn(coeffs, blocks):
    """(R, K) coefficients x (K, D) stacked blocks -> (R, D)."""
    return jnp.dot(coeffs, blocks, precision=lax.Precision.HIGHEST,
                   preferred_element_type=blocks.dtype)


combine_jit = jax.jit(_combine_fn)


def _combine_kernel(coeff_ref, block_ref, out_ref):
    out_ref[...] = _combine_fn(coeff_ref[...], block_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_pallas(coeffs, blocks, interpret=None):
    """One Pallas program over the whole (R, K) x (K, D) combine."""
    n_rows, k = coeffs.shape
    k2, d = blocks.shape
    if k != k2:
        raise ValueError(f"coeffs k={k} != blocks k={k2}")
    return pl.pallas_call(
        _combine_kernel,
        out_shape=jax.ShapeDtypeStruct((n_rows, d), blocks.dtype),
        interpret=resolve_interpret(interpret),
        name="coded_combine",
    )(coeffs, blocks)
