"""Backend dispatch + wall-clock overhead probe for coded encode/decode.

:func:`coded_combine` is the one seam both dispatch boundaries go
through: encode is ``combine(G (n, k), blocks (k, d))`` before dispatch,
decode is ``combine(W (k', m), responses (m, d))`` on the k-th
completion.  :func:`measure_coding_overhead` times both (plus the
decode-weight solve) on the requested backend and returns seconds — the
numbers the planner writes into a ``CodingCandidate`` whose overheads
were left ``None``, so the sweep's coded completion samples carry the
cost the scheme actually pays instead of assuming it free.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

BACKENDS = ("numpy", "jax", "pallas")


def coded_combine(coeffs, blocks, *, backend: str = "numpy",
                  interpret: Optional[bool] = None):
    """(R, K) coefficient rows x (K, D) stacked blocks -> (R, D) coded rows.

    ``backend="numpy"`` is the host reference; ``"jax"`` / ``"pallas"``
    run the shared kernel body of :mod:`.kernel` (Pallas in interpret mode
    by default only on a CPU backend).
    """
    if backend == "numpy":
        return np.asarray(coeffs) @ np.asarray(blocks)
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r} (expected one of {BACKENDS})")
    import jax.numpy as jnp

    from . import kernel as _kernel

    fdtype = jnp.result_type(float)
    coeffs = jnp.asarray(coeffs, fdtype)
    blocks = jnp.asarray(blocks, fdtype)
    if backend == "pallas":
        return _kernel.combine_pallas(coeffs, blocks, interpret=interpret)
    return _kernel.combine_jit(coeffs, blocks)


def decode_combine(weights, responses, *, backend: str = "numpy",
                   interpret: Optional[bool] = None):
    """Decode-side combine: same kernel, (k', m) weights x (m, d) responses."""
    return coded_combine(weights, responses, backend=backend,
                         interpret=interpret)


def encode_matrix(candidate, n_workers: int) -> np.ndarray:
    """The scheme's (n_workers, n_blocks) encode/coefficient matrix.

    * cyclic — Tandon coefficients over the N unit batches (cyclic
      support, any N-s rows span the all-ones decode target);
    * mds / poly — the real Vandermonde generator at Chebyshev nodes
      (for poly this is the evaluation matrix over the k = m*p product
      blocks; the A- and B-side encodes are its m- and p-column slices).
    """
    from repro.core.coding import CodingCandidate, MDSCode
    from repro.core.gradient_coding import CyclicGradientCode

    if not isinstance(candidate, CodingCandidate):
        raise TypeError(
            f"expected CodingCandidate, got {type(candidate).__name__}")
    k = candidate.k(n_workers)
    if candidate.scheme == "cyclic":
        return CyclicGradientCode(n_workers, candidate.s).coefficients()
    return MDSCode(n_workers, k).generator()


def _decode_solver(candidate, n_workers: int, gen: np.ndarray):
    """Host-side solve producing the decode weight matrix for the first-k
    completion subset (part of the measured decode cost)."""
    from repro.core.gradient_coding import CyclicGradientCode

    k = candidate.k(n_workers)
    alive = np.zeros(n_workers, dtype=bool)
    alive[:k] = True
    if candidate.scheme == "cyclic":
        code = CyclicGradientCode(n_workers, candidate.s)

        def solve():
            return code.decode_weights(alive)[None, :]  # (1, k)
    else:
        g_alive = gen[alive]

        def solve():
            return np.linalg.inv(g_alive)  # (k, k)
    return alive, solve


def _best_of(fn, repeats: int) -> float:
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return float(best)


def measure_coding_overhead(
    candidate,
    n_workers: int,
    *,
    block_dim: int = 2048,
    repeats: int = 3,
    seed: int = 0,
    backend: str = "numpy",
    interpret: Optional[bool] = None,
) -> tuple[float, float]:
    """Wall-clock (encode_seconds, decode_seconds) of one coded job.

    Encode: the coefficient-combine over the data blocks before dispatch
    (doubled for ``poly``, which encodes both factors).  Decode: the
    weight solve for the first-k completion subset plus the combine over
    the k responses.  Min-of-``repeats`` after one warmup call, so jit
    compilation is excluded and scheduler noise is suppressed.  The
    returned seconds are commensurate with service times measured in
    seconds — the cluster runtime's wall-clock telemetry and the
    benchmarks use exactly that convention.
    """
    gen = encode_matrix(candidate, n_workers)
    k_blocks = gen.shape[1]
    rng = np.random.default_rng(seed)
    blocks = rng.standard_normal((k_blocks, block_dim))
    n_encodes = 2 if candidate.scheme == "poly" else 1

    def encode():
        out = None
        for _ in range(n_encodes):
            out = coded_combine(gen, blocks, backend=backend,
                                interpret=interpret)
        return out

    encode()  # warmup (jit/pallas trace)
    enc = _best_of(encode, repeats)

    alive, solve = _decode_solver(candidate, n_workers, gen)
    responses = gen[alive] @ blocks

    def decode():
        return decode_combine(solve(), responses, backend=backend,
                              interpret=interpret)

    decode()  # warmup
    dec = _best_of(decode, repeats)
    return enc, dec
