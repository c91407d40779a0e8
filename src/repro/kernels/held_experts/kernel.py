"""Grouped matmul over one layer's held experts, read in place from the
stack of every layer's (``held_experts_gmm``).

The rows ``x`` (m, k) are (token, expert) pairs sorted by expert: rows
``[offsets[e], offsets[e + 1])`` go through expert ``e``, and rows past
the last group through none.  The weights are the whole stack ``w`` (L,
E, k, n) of every layer's experts; the layer index and the visit list
(which experts, and how many, the grid visits) arrive as scalar prefetch,
so that the index map picks ``(layer, expert, k-tile, n-tile)`` blocks
straight out of HBM and no layer's stack is copied out first.

* grid = (E visit slots, n tiles, k tiles).  Slot ``v < count`` streams
  expert ``experts[v]``'s weight blocks once each; every slot after the
  last real one maps to the block that visit ended on, so the pipeline
  fetches nothing more for it, and the body skips it.  An expert with no
  row is never fetched, except where no expert has a row: the pipeline
  then still fetches its first block, before any step runs.
* The output (m, n) and a float32 accumulator stay in VMEM for the whole
  grid: each real step adds the product of all m rows with the block,
  masked to the visited expert's rows, and the last step writes the
  output in its dtype.  Rows in no group are zero.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

__all__ = ["held_experts_gmm_kernel_call", "weight_block", "rows_block"]


def _clamped(v, j, kk, count_ref, tiles_n, tiles_k):
    """(n tile, k tile) of step (v, j, kk); after the last real visit,
    those the last real step read."""
    live = v < count_ref[0]
    return (jnp.where(live, j, tiles_n - 1), jnp.where(live, kk, tiles_k - 1))


def weight_block(v, j, kk, layer_ref, experts_ref, count_ref, offsets_ref, *,
                 tiles_n, tiles_k):
    """Block index of the weight stack (L, E, k, n) at grid step (v, j, kk):
    ``(layer, experts[v], k tile, n tile)``."""
    del offsets_ref
    jn, jk = _clamped(v, j, kk, count_ref, tiles_n, tiles_k)
    return layer_ref[0], experts_ref[v], jk, jn


def rows_block(v, j, kk, layer_ref, experts_ref, count_ref, offsets_ref, *,
               tiles_n, tiles_k):
    """Block index of the rows (m, k) at grid step (v, j, kk)."""
    del layer_ref, experts_ref, offsets_ref
    return 0, _clamped(v, j, kk, count_ref, tiles_n, tiles_k)[1]


def _gmm_kernel(layer_ref, experts_ref, count_ref, offsets_ref, x_ref, w_ref,
                o_ref, acc_ref, *, tn, tiles_n, tiles_k):
    del layer_ref
    v, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((v == 0) & (j == 0) & (kk == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(v < count_ref[0])
    def _accumulate():
        e = experts_ref[v]
        part = jnp.dot(x_ref[...], w_ref[...],
                       preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, part.shape, 0)
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        cols = (pl.ds(pl.multiple_of(j * tn, tn), tn) if tiles_n > 1
                else slice(None))
        acc_ref[:, cols] += jnp.where(mine, part, 0.0)

    @pl.when((v == pl.num_programs(0) - 1) & (j == tiles_n - 1)
             & (kk == tiles_k - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def held_experts_gmm_kernel_call(x, w, layer, offsets, experts, count, *,
                                 tk: int, tn: int,
                                 interpret: Optional[bool] = None):
    """x (m, k); w (L, E, k, n); layer, count int32 scalars; offsets (E + 1,)
    and experts (E,) int32.  Returns (m, n) in x.dtype: row r of expert
    e's group is x[r] @ w[layer, e], accumulated in float32; rows in no
    group are zero.  ``tk``, ``tn`` divide k and n."""
    m, k = x.shape
    _, e, _, n = w.shape
    tiles_k, tiles_n = k // tk, n // tn
    assert tiles_k * tk == k and tiles_n * tn == n, (k, n, tk, tn)
    maps = dict(tiles_n=tiles_n, tiles_k=tiles_k)
    kernel = functools.partial(_gmm_kernel, tn=tn, **maps)
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32),
               experts.astype(jnp.int32),
               jnp.reshape(count, (1,)).astype(jnp.int32),
               offsets.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(e, tiles_n, tiles_k),
            in_specs=[
                pl.BlockSpec((m, tk), functools.partial(rows_block, **maps)),
                pl.BlockSpec((None, None, tk, tn),
                             functools.partial(weight_block, **maps)),
            ],
            out_specs=pl.BlockSpec((m, n), lambda *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((m, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(e * k * n * w.dtype.itemsize
                            + m * (k + n) * x.dtype.itemsize)),
        interpret=resolve_interpret(interpret),
        name="held_experts_gmm",
    )(*scalars, x, w)
