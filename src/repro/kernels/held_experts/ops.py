"""Public entry for the held-expert grouped matmul: picks the weight block
shape and calls the kernel (``interpret`` defaults to the platform)."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.held_experts.kernel import held_experts_gmm_kernel_call

__all__ = ["held_experts_gmm", "block_shape", "BLOCK_BYTES"]

# weight bytes a grid step streams: large enough that a step's fixed cost
# hides behind its copy, small enough that the first block of a call,
# which nothing overlaps, costs little
BLOCK_BYTES = 3 << 19


def _splits(dim: int) -> list[int]:
    """Tile sizes a block may take along ``dim``: the multiples of 128 that
    divide it, and the whole dim."""
    return [t for t in range(128, dim, 128) if dim % t == 0] + [dim]


def block_shape(k: int, n: int, itemsize: int,
                budget: int = BLOCK_BYTES) -> tuple[int, int]:
    """(tk, tn) of the largest weight block within ``budget`` bytes (the
    wider one of two the same size); the smallest block where none fits."""
    fits = [(tk, tn) for tk in _splits(k) for tn in _splits(n)
            if tk * tn * itemsize <= budget]
    if not fits:
        return _splits(k)[0], _splits(n)[0]
    return max(fits, key=lambda b: (b[0] * b[1], b[1]))


def held_experts_gmm(x, w, layer, sizes, experts, count, *,
                     interpret: Optional[bool] = None):
    """Grouped matmul of the rows x (m, k), sorted by expert into groups of
    ``sizes`` (E,) rows (rows past their sum in no group), with layer
    ``layer`` of the stacked weights w (L, E, k, n): (m, n) in x.dtype,
    zero in rows of no group.  ``experts`` (E,) and ``count`` are the
    visit list (``repro.models.moe.touched_experts``): only those experts'
    weights are read."""
    tk, tn = block_shape(w.shape[2], w.shape[3], w.dtype.itemsize)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes).astype(jnp.int32)])
    return held_experts_gmm_kernel_call(x, w, layer, offsets, experts, count,
                                        tk=tk, tn=tn, interpret=interpret)
