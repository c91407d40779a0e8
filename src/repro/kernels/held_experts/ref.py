"""Pure-jnp oracle for the held-expert grouped matmul."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["held_experts_gmm_ref"]


def held_experts_gmm_ref(x, w, layer, sizes):
    """``jax.lax.ragged_dot`` of x (m, k) with w[layer] (E, k, n) in groups
    of ``sizes``, rows in no group set to zero."""
    y = jax.lax.ragged_dot(x, w[layer], sizes)
    live = jnp.arange(x.shape[0]) < sizes.sum()
    return jnp.where(live[:, None], y, jnp.zeros((), y.dtype))
