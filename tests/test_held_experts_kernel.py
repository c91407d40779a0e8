"""The decode step's held-expert grouped matmul (``held_experts_gmm``) in
interpret mode: the same products as ``jax.lax.ragged_dot`` over the
layer it is given of a stack of layers, and weight blocks fetched for the
experts that have a pair only (``moe.touched_experts``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.held_experts import kernel as HK
from repro.kernels.held_experts.ops import block_shape, held_experts_gmm
from repro.kernels.held_experts.ref import held_experts_gmm_ref
from repro.models.moe import touched_experts

LAYERS, E, K, N, M = 3, 8, 256, 256, 12
# blocks of 128 x 128: two k tiles and two n tiles an expert
TK = TN = 128

SIZES = {
    # experts with no pair between those with some; 2 rows in no group
    "empty_experts": [3, 0, 2, 0, 0, 4, 0, 1],
    "one_expert_takes_every_pair": [0, 0, 0, 12, 0, 0, 0, 0],
    "no_pair_held": [0] * 8,
    "every_expert_one_pair": [1] * 8,
    "every_row_held": [2, 1, 3, 0, 1, 2, 2, 1],
}


def _operands(dtype, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (LAYERS, E, K, N), jnp.float32)
         * K ** -0.5).astype(dtype)
    return x, w


def _gmm(x, w, layer, sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    experts, count = touched_experts(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    return HK.held_experts_gmm_kernel_call(x, w, layer, offsets, experts,
                                           count, tk=TK, tn=TN)


def _exact(x, w, layer, sizes):
    """float64 numpy, row by row: x[r] @ w[layer, e] for row r of expert
    e's group, zero past the groups; and the sum of |terms| of each."""
    x, w = np.asarray(x, np.float64), np.asarray(w[layer], np.float64)
    out, mag = np.zeros((M, N)), np.zeros((M, N))
    ends = np.cumsum(sizes)
    for r in range(int(ends[-1])):
        e = int(np.searchsorted(ends, r, side="right"))
        out[r], mag[r] = x[r] @ w[e], np.abs(x[r]) @ np.abs(w[e])
    return out, mag


@pytest.mark.parametrize("case", SIZES)
def test_float32_equals_ragged_dot(case):
    x, w = _operands(jnp.float32)
    got = _gmm(x, w, 1, SIZES[case])
    want = held_experts_gmm_ref(x, w, 1, jnp.asarray(SIZES[case], jnp.int32))
    assert got.dtype == want.dtype == jnp.float32
    scale = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * scale)
    exact, _ = _exact(x, w, 1, SIZES[case])
    np.testing.assert_allclose(np.asarray(got), exact, rtol=1e-5,
                               atol=1e-5 * scale)


@pytest.mark.parametrize("case", SIZES)
def test_bfloat16_rounds_as_ragged_dot(case):
    """bfloat16 operands and output, float32 sums: each entry within half a
    bfloat16 unit of the exact product (2**-8 relative) plus the float32
    sum's rounding, as ``ragged_dot``'s output is."""
    x, w = _operands(jnp.bfloat16)
    sizes = SIZES[case]
    got = _gmm(x, w, 2, sizes)
    want = held_experts_gmm_ref(x, w, 2, jnp.asarray(sizes, jnp.int32))
    assert got.dtype == want.dtype == jnp.bfloat16
    exact, mag = _exact(x, w, 2, sizes)
    tol = 2.0 ** -8 * np.abs(exact) + 1e-6 * mag
    for y in (got, want):
        assert (np.abs(np.asarray(y, np.float64) - exact) <= tol).all()


def test_layer_index_picks_the_layer():
    x, w = _operands(jnp.float32, seed=1)
    sizes = SIZES["every_row_held"]
    for layer in range(LAYERS):
        got = np.asarray(_gmm(x, w, layer, sizes))
        for other in range(LAYERS):
            want, _ = _exact(x, w, other, sizes)
            close = np.allclose(got, want, rtol=1e-5, atol=1e-4)
            assert close == (other == layer), (layer, other)


def test_the_kernel_runs_under_a_scan_over_a_closed_over_stack():
    """As ``mla.decode`` calls it: the stack closed over, the layer index a
    scan input."""
    x, w = _operands(jnp.float32, seed=2)
    sizes = SIZES["empty_experts"]

    def body(h, layer):
        return h, _gmm(x, w, layer, sizes)

    _, ys = jax.jit(lambda: jax.lax.scan(body, 0, jnp.arange(LAYERS)))()
    for layer in range(LAYERS):
        want, _ = _exact(x, w, layer, sizes)
        np.testing.assert_allclose(np.asarray(ys[layer]), want, rtol=1e-5,
                                   atol=1e-4)


@pytest.mark.parametrize("case", SIZES)
def test_touched_experts_are_those_with_a_pair(case):
    sizes = np.asarray(SIZES[case])
    ids, count = touched_experts(jnp.asarray(sizes, jnp.int32))
    ids, count = np.asarray(ids), int(count)
    assert count == int((sizes > 0).sum())
    np.testing.assert_array_equal(ids[:count], np.flatnonzero(sizes > 0))
    # the padding repeats the last visit (expert 0 where none is touched)
    assert (ids[count:] == (ids[count - 1] if count else 0)).all()


def _fetched(sizes, layer, tiles_n, tiles_k):
    """The weight blocks the pipeline copies in: the block of the first grid
    step, and each step's block that differs from the step before."""
    ids, count = touched_experts(jnp.asarray(sizes, jnp.int32))
    scalars = (np.array([layer]), np.asarray(ids), np.array([int(count)]),
               np.concatenate([[0], np.cumsum(sizes)]))
    blocks, last = [], None
    for v in range(len(sizes)):
        for j in range(tiles_n):
            for kk in range(tiles_k):
                b = tuple(int(i) for i in HK.weight_block(
                    v, j, kk, *scalars, tiles_n=tiles_n, tiles_k=tiles_k))
                if b != last:
                    blocks.append(b)
                last = b
    return blocks


@pytest.mark.parametrize("case", SIZES)
def test_index_map_fetches_the_touched_experts_blocks_once(case):
    sizes = np.asarray(SIZES[case])
    tiles_n, tiles_k = N // TN, K // TK
    blocks = _fetched(sizes, 1, tiles_n, tiles_k)
    assert {b[0] for b in blocks} == {1}
    touched = np.flatnonzero(sizes > 0)
    if not len(touched):
        # the pipeline's first block, before any step runs; nothing more
        assert len(blocks) == 1
        return
    assert len(blocks) == len(set(blocks))
    assert sorted(blocks) == sorted(
        (1, int(e), kk, j) for e in touched for kk in range(tiles_k)
        for j in range(tiles_n))


def test_entry_point_picks_the_block_and_equals_the_kernel():
    x, w = _operands(jnp.float32, seed=3)
    sizes = jnp.asarray(SIZES["empty_experts"], jnp.int32)
    experts, count = touched_experts(sizes)
    got = held_experts_gmm(x, w, jnp.int32(0), sizes, experts, count)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_gmm(x, w, 0, SIZES["empty_experts"])),
                               rtol=1e-5, atol=1e-5)


def test_block_shape_at_the_served_widths():
    """DeepSeek-V2-Lite's experts (2048 x 1408 and 1408 x 2048, bfloat16):
    1.44 MB blocks, whole along the 1408-wide dim, which 128 divides 11
    times only."""
    assert block_shape(2048, 1408, 2) == (512, 1408)
    assert block_shape(1408, 2048, 2) == (1408, 512)
