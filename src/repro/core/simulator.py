"""Monte-Carlo simulator of the paper's System1 — batched + vectorized.

Three public entry points:

* :func:`simulate_maxmin` — the paper's completion rule for non-overlapping
  balanced replication, fully vectorized: ``T = max_i min_j T_ij``.
* :func:`simulate_coverage` — general rule for ANY :class:`Assignment`
  (overlapping, unbalanced): completion is the first time the union of
  finished workers' batches covers the dataset.  Vectorized over trials AND
  workers via a sort + cumulative bitwise-OR prefix-coverage scan (bitmask
  words, ``argmax`` of the first fully-covered prefix).  The original
  per-trial Python loop is retained as :func:`simulate_coverage_reference`
  and shares the exact same draws, so the two are bit-for-bit comparable.
* :func:`sweep_simulate` — the batched engine: evaluates ALL feasible
  (B, r) splits of N for one or several service distributions in a single
  call, from ONE shared matrix of unit-exponential draws (common random
  numbers, so cross-(B, dist) comparisons are variance-reduced).  Backends:
  ``"numpy"`` (default) and ``"jax"`` (``jax.vmap`` over splits +
  distributions, jit-compiled ``segment_min`` reduction).

Heterogeneous workers: every sampling path accepts an optional ``rates``
vector of per-worker relative service rates (worker ``j`` runs at rate
``mu * rates[j]``; ``rates[j] < 1`` is a slow node).  With ``rates`` equal
to ones the heterogeneous paths reproduce the homogeneous results
bit-for-bit (same RNG stream, same float ops).

Service times follow the size-dependent model: a worker serving ``s`` units
of data at rate multiplier ``c`` draws ``s*Delta + E / (mu*c/s)`` with
``E ~ Exp(1)`` — i.e. ``dist.scaled(s)`` with its exponential part slowed by
``1/c``.

The engine is distribution-agnostic: besides the paper's Exp/SExp families
it accepts :class:`~repro.core.order_stats.Empirical` (ECDF) distributions
on EVERY sampling path — batch-completion sweeps (numpy and jax backends),
sojourn/queueing and straggler-policy sweeps, and the runtime
:class:`StepTimeSimulator`.  Empirical sampling stays on the shared CRN
draw matrix via quantile coupling (see :func:`_empirical_coupled_times`):
uniform positions derived from the shared exponential draws are pushed
through the empirical quantile function, so empirical and parametric sweep
cells remain directly comparable — and an empirical pool built from an
exact monotone transform of the draws is bit-identical to the parametric
sweep, the parity contract ``tests/test_sim_engine.py`` pins.

Also provides :class:`StepTimeSimulator` — the runtime-facing generator of
per-step, per-worker service times (with optional persistent slow nodes,
per-worker base rates, and transient failures) used by the fault-tolerance
harness and the tuner tests.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Sequence

import numpy as np

from .coding import CodingCandidate
from .order_stats import Empirical, ServiceDistribution
from .policies import (
    Assignment,
    PolicyCandidate,
    ShedPolicy,
    SloClass,
    _validate_rates,
    divisors,
)

__all__ = [
    "SimResult",
    "SweepSimResult",
    "PolicySweepResult",
    "CodedSweepResult",
    "ServingSweepResult",
    "ServingSimResult",
    "simulate_maxmin",
    "simulate_coverage",
    "simulate_coverage_reference",
    "simulate_sojourn",
    "simulate_sojourn_policies",
    "simulate_sojourn_serving",
    "sweep_simulate",
    "sweep_coded",
    "sweep_sojourn",
    "sweep_sojourn_policies",
    "sweep_sojourn_coded",
    "sweep_sojourn_serving",
    "resolve_sweep_backend",
    "SWEEP_BACKENDS",
    "censored_observations",
    "StepTimeSimulator",
    "FaultEvent",
]


@dataclasses.dataclass(frozen=True)
class SimResult:
    samples: np.ndarray  # (n_trials,) completion times

    @property
    def mean(self) -> float:
        return float(self.samples.mean())

    @property
    def var(self) -> float:
        return float(self.samples.var(ddof=1))

    @property
    def std(self) -> float:
        return float(self.samples.std(ddof=1))

    def quantile(self, q: float) -> float:
        return float(np.quantile(self.samples, q))

    @property
    def stderr(self) -> float:
        return float(self.samples.std(ddof=1) / np.sqrt(len(self.samples)))


# ---------------------------------------------------------------------------
# shared sampling core
# ---------------------------------------------------------------------------


def _dist_params(dist: ServiceDistribution) -> tuple[float, float]:
    """(shift, mu) of the unit-load service distribution.

    The engine exploits that Exp/SExp scale affinely with load:
    ``scaled(s) = s*shift + Exp(1)*s/mu``.  Any distribution exposing ``mu``
    (and optionally ``delta``) participates; :class:`~repro.core.order_stats
    .Empirical` takes the quantile-lookup path instead; others are rejected.
    """
    mu = getattr(dist, "mu", None)
    if mu is None:
        raise TypeError(
            f"{type(dist).__name__} must expose 'mu' (and optional 'delta') "
            "for the vectorized engine (or be an Empirical distribution)"
        )
    return float(getattr(dist, "delta", 0.0)), float(mu)


def _empirical_coupled_times(
    dist: Empirical, unit: np.ndarray, order: np.ndarray | None = None
) -> np.ndarray:
    """Quantile-coupled empirical times from the SHARED Exp(1) draw matrix.

    The CRN contract of the engine: every cell of a sweep consumes the same
    draw matrix, so cross-cell differences are pure policy/distribution
    effects.  For an empirical distribution that coupling is realized by
    RANK: the flattened draws are replaced by the inverse weighted-ECDF
    evaluated at the stratified levels ``(2k+1)/(2M)`` in draw-rank order —
    draw ``k``-th-smallest maps to the ``k``-th stratified ECDF quantile.
    Equivalently: uniform draws (the probability-integral transform of the
    shared exponentials) pushed through the empirical quantile function,
    with the uniforms' VALUES replaced by their plotting positions.

    Two properties make this the right coupling:

    * comparisons against any parametric cell of the same sweep see the
      same randomness (the arrangement across trials/workers is exactly the
      shared draws' rank pattern), and
    * a pool that IS a monotone transform of the exact draws reproduces
      that transform **bit-for-bit** — the uniform-weight fast path indexes
      with pure-integer arithmetic (``(2k+1)*n // (2M)`` = ``k`` when
      ``n == M``), so ``Empirical((shift + unit/mu).ravel())`` yields
      ``shift + unit/mu`` exactly.  That is the parity pin keeping the
      empirical engine path honest against the parametric one.
    """
    flat = unit.ravel()
    m = flat.size
    if order is None:
        order = np.argsort(flat, kind="stable")
    n = dist.n_atoms
    if dist.weights is None:
        idx = (2 * np.arange(m) + 1) * n // (2 * m)
        vals = dist._atoms_arr[idx]
    else:
        levels = (2.0 * np.arange(m) + 1.0) / (2.0 * m)
        vals = dist.ppf(levels)
    out = np.empty(m)
    out[order] = vals
    return out.reshape(unit.shape)


def _unit_times(
    unit: np.ndarray,
    dist: ServiceDistribution,
    rates: np.ndarray | None,
    iid: bool = False,
    order: np.ndarray | None = None,
) -> np.ndarray:
    """Unit-load service times from shared Exp(1) draws.

    Parametric (Exp/SExp-shaped): ``shift + E/(mu*rate)``.  ``rates=None``
    and ``rates=ones`` are bit-identical (``mu * 1.0 == mu`` exactly, so
    the elementwise divisor is the same float either way).

    Empirical: inverse-ECDF on the shared draws — rank-coupled
    (:func:`_empirical_coupled_times`) for the batched sweep matrices,
    plain i.i.d. probability-integral lookup with ``iid=True`` (the
    per-step :class:`StepTimeSimulator` path, where a rank coupling over a
    single N-vector would degenerate to the same N quantiles every step).
    An empirical time has no shift/exponential decomposition, so a rate
    multiplier scales the WHOLE draw (``t / rate``).
    """
    if isinstance(dist, Empirical):
        if iid:
            core = dist.ppf(-np.expm1(-unit))
        else:
            core = _empirical_coupled_times(dist, unit, order=order)
        return core if rates is None else core / rates
    shift, mu = _dist_params(dist)
    denom = mu if rates is None else mu * rates
    return shift + unit / denom


def _shared_draw_order(
    dists: Sequence[ServiceDistribution], unit: np.ndarray
) -> np.ndarray | None:
    """Hoist the coupling argsort of one shared draw matrix.

    The rank pattern of the draws is distribution-independent, so a sweep
    over many empirical dists (K bootstrap resamples of one telemetry pool
    is the common case) sorts ONCE instead of once per dist — the argsort
    is the dominant per-resample cost at planner trial counts.
    """
    if any(isinstance(d, Empirical) for d in dists):
        return np.argsort(unit.ravel(), kind="stable")
    return None


def _times_from_unit(
    unit: np.ndarray,
    loads: np.ndarray,
    dist: ServiceDistribution,
    rates: np.ndarray | None,
    iid: bool = False,
) -> np.ndarray:
    """Worker service times ``loads_j * unit_time_j``.

    Factored so the batched sweep can hoist the load-independent inner
    matrix; multiplying by a constant-load vector equals the scalar multiply
    bit-for-bit, which keeps sweep cells identical to simulate_maxmin.
    """
    return _unit_times(unit, dist, rates, iid=iid) * loads


def _draw_worker_times(
    dist: ServiceDistribution,
    loads: np.ndarray,
    n_trials: int,
    seed: int,
    rates: np.ndarray | None = None,
) -> np.ndarray:
    """(n_trials, N) service times; the single RNG touchpoint of the engine."""
    rng = np.random.default_rng(seed)
    unit = rng.standard_exponential((n_trials, len(loads)))
    return _times_from_unit(unit, loads, dist, rates)


# ---------------------------------------------------------------------------
# max-min (balanced non-overlapping) fast path
# ---------------------------------------------------------------------------


def simulate_maxmin(
    dist: ServiceDistribution,
    n_workers: int,
    n_batches: int,
    n_trials: int = 20_000,
    seed: int = 0,
    rates: Sequence[float] | None = None,
) -> SimResult:
    """Completion time of balanced non-overlapping replication (fast path).

    ``rates`` (optional, length N): per-worker relative service rates; the
    contiguous worker->batch map of :func:`balanced_nonoverlapping` is used
    (worker j serves batch j // r).  Shares the RNG stream of
    :func:`sweep_simulate`, so a single-split sweep is bit-identical.
    """
    if n_workers % n_batches:
        raise ValueError(f"B={n_batches} must divide N={n_workers}")
    r = n_workers // n_batches
    rates_arr = _validate_rates(rates, n_workers)
    loads = np.full(n_workers, n_workers / n_batches)
    times = _draw_worker_times(dist, loads, n_trials, seed, rates_arr)
    completion = times.reshape(n_trials, n_batches, r).min(axis=2).max(axis=1)
    return SimResult(completion)


# ---------------------------------------------------------------------------
# coverage rule (arbitrary assignments)
# ---------------------------------------------------------------------------


def _pack_coverage(assignment: Assignment) -> tuple[np.ndarray, np.ndarray]:
    """Per-worker coverage bitmasks.

    Returns (masks, full): masks is (N, W) uint64 with W = ceil(units/64);
    full is the (W,) all-units mask.  Bitwise-OR of masks across workers is
    the union of their covered units.
    """
    cov = assignment.coverage_matrix()  # (N, units) bool
    n, units = cov.shape
    words = (units + 63) // 64
    masks = np.zeros((n, words), dtype=np.uint64)
    full = np.zeros(words, dtype=np.uint64)
    for w in range(words):
        chunk = cov[:, w * 64 : (w + 1) * 64]
        weights = np.uint64(1) << np.arange(chunk.shape[1], dtype=np.uint64)
        masks[:, w] = (chunk.astype(np.uint64) * weights).sum(axis=1)
        full[w] = weights.sum()
    return masks, full


def simulate_coverage(
    dist: ServiceDistribution,
    assignment: Assignment,
    n_trials: int = 20_000,
    seed: int = 0,
    rates: Sequence[float] | None = None,
) -> SimResult:
    """Completion time under the coverage rule for arbitrary assignments.

    Fully vectorized: draw all worker times, argsort per trial, cumulative
    bitwise-OR of per-worker coverage bitmasks along the sorted-worker axis,
    ``argmax`` of the first prefix whose union covers every unit.  O(trials*N)
    numpy ops, no Python loop over trials.
    """
    loads = assignment.worker_load()  # (N,)
    rates_arr = _validate_rates(rates, assignment.n_workers)
    times = _draw_worker_times(dist, loads, n_trials, seed, rates_arr)

    masks, full = _pack_coverage(assignment)  # (N, W), (W,)
    order = np.argsort(times, axis=1)  # (trials, N)
    sorted_times = np.take_along_axis(times, order, axis=1)
    cum = np.bitwise_or.accumulate(masks[order], axis=1)  # (trials, N, W)
    covered = (cum == full[None, None, :]).all(axis=2)  # (trials, N)
    first = covered.argmax(axis=1)  # valid: Assignment guarantees full coverage
    completion = np.take_along_axis(sorted_times, first[:, None], axis=1)[:, 0]
    return SimResult(completion)


def simulate_coverage_reference(
    dist: ServiceDistribution,
    assignment: Assignment,
    n_trials: int = 20_000,
    seed: int = 0,
    rates: Sequence[float] | None = None,
) -> SimResult:
    """Reference implementation: per-trial Python walk over sorted workers.

    Draws the SAME times as :func:`simulate_coverage` (shared sampling core),
    so results are bit-for-bit equal; kept as the oracle for property tests
    and as the benchmark baseline.
    """
    loads = assignment.worker_load()
    rates_arr = _validate_rates(rates, assignment.n_workers)
    times = _draw_worker_times(dist, loads, n_trials, seed, rates_arr)

    masks, full = _pack_coverage(assignment)
    n = assignment.n_workers
    order = np.argsort(times, axis=1)
    sorted_times = np.take_along_axis(times, order, axis=1)
    completion = np.empty(n_trials, dtype=float)
    for t in range(n_trials):
        acc = np.zeros_like(full)
        done_time = sorted_times[t, -1]
        for k in range(n):
            acc |= masks[order[t, k]]
            if np.array_equal(acc, full):
                done_time = sorted_times[t, k]
                break
        completion[t] = done_time
    return SimResult(completion)


# ---------------------------------------------------------------------------
# batched sweep over (B, r) splits x distributions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepSimResult:
    """Samples for every (distribution, split) pair of one batched sweep.

    ``samples[d, s]`` holds the completion times for ``dists[d]`` at
    ``splits[s]`` batches, all generated from the same unit-exponential draw
    matrix (common random numbers), so differences across cells are pure
    policy/distribution effects.
    """

    n_workers: int
    splits: tuple[int, ...]
    dists: tuple[ServiceDistribution, ...]
    samples: np.ndarray  # (n_dists, n_splits, n_trials)
    backend: str

    def result(self, n_batches: int, dist_index: int = 0) -> SimResult:
        return SimResult(self.samples[dist_index, self.splits.index(n_batches)])

    def means(self) -> np.ndarray:
        """(n_dists, n_splits) empirical mean completion times."""
        return self.samples.mean(axis=2)

    def variances(self) -> np.ndarray:
        return self.samples.var(axis=2, ddof=1)

    def best_mean(self, dist_index: int = 0) -> tuple[int, float]:
        """(argmin-B, mean) for one distribution."""
        m = self.means()[dist_index]
        k = int(np.argmin(m))
        return self.splits[k], float(m[k])

    def table(self, dist_index: int = 0) -> dict[int, SimResult]:
        return {
            b: SimResult(self.samples[dist_index, i])
            for i, b in enumerate(self.splits)
        }


def _normalize_dists(
    dists: ServiceDistribution | Sequence[ServiceDistribution],
) -> tuple[ServiceDistribution, ...]:
    if isinstance(dists, ServiceDistribution):
        return (dists,)
    out = tuple(dists)
    if not out:
        raise ValueError("at least one distribution required")
    return out


def _split_arrays(
    n_workers: int, splits: Sequence[int], worker_batches=None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static per-split arrays: loads (S, N), worker->batch ids (S, N),
    valid-batch-slot mask (S, N) — fixed shapes so the JAX backend can vmap.
    ``worker_batches`` overrides the contiguous grouping per split (the
    rate-aware placements); loads stay ``N/B`` (total data split B ways)."""
    s_count = len(splits)
    loads = np.empty((s_count, n_workers))
    wb = np.empty((s_count, n_workers), dtype=np.int32)
    valid = np.zeros((s_count, n_workers), dtype=bool)
    for i, b in enumerate(splits):
        loads[i] = n_workers / b
        if worker_batches is None:
            wb[i] = np.arange(n_workers) // (n_workers // b)
        else:
            wb[i] = worker_batches[i]
        valid[i, :b] = True
    return loads, wb, valid


_JAX_KERNEL_CACHE: dict = {}


def _sweep_jax(
    cores: np.ndarray,
    loads: np.ndarray,
    wb: np.ndarray,
    valid: np.ndarray,
    indices_sorted: bool = True,
) -> np.ndarray:
    """JAX backend: vmap over distributions x splits, jit-compiled.

    ``cores`` is the (n_dists, T, N) stack of load-independent unit-load
    times, precomputed in numpy by the SAME :func:`_unit_times` the numpy
    backend uses — which is what lets parametric and empirical
    distributions share one kernel (and keeps empirical-vs-parametric
    bit-parity intact through the jit boundary: identical f64 cores cast
    to the device dtype identically).  Per split the min-over-replicas is
    a ``segment_min`` keyed by the worker->batch map (padded to N
    segments, invalid slots masked to -inf before the max), which keeps
    every split the same shape and therefore vmappable.
    """
    import jax
    import jax.numpy as jnp

    key = ("kernel", indices_sorted)
    if key not in _JAX_KERNEL_CACHE:

        def kernel(cores, loads, wb, valid):
            n = cores.shape[2]

            def one_dist(core):
                def one_split(loads_row, wb_row, valid_row):
                    times = core * loads_row  # (T, N)
                    bmin = jax.ops.segment_min(
                        times.T, wb_row, num_segments=n,
                        indices_are_sorted=indices_sorted,
                    )  # (N, T)
                    bmin = jnp.where(valid_row[:, None], bmin, -jnp.inf)
                    return bmin.max(axis=0)  # (T,)

                return jax.vmap(one_split)(loads, wb, valid)

            return jax.vmap(one_dist)(cores)

        _JAX_KERNEL_CACHE[key] = jax.jit(kernel)

    out = _JAX_KERNEL_CACHE[key](cores, loads, wb, valid)
    return np.asarray(out, dtype=float)


def sweep_simulate(
    dists: ServiceDistribution | Sequence[ServiceDistribution],
    n_workers: int,
    n_trials: int = 20_000,
    seed: int = 0,
    feasible_b: Sequence[int] | None = None,
    rates: Sequence[float] | None = None,
    backend: str = "numpy",
    worker_batches: Sequence[Sequence[int]] | None = None,
) -> SweepSimResult:
    """Simulate ALL feasible (B, r) splits x distributions in one batched call.

    One (n_trials, N) matrix of Exp(1) draws is shared by every cell (common
    random numbers): comparisons across B or across distributions see the
    same randomness, which collapses the variance of their differences.

    ``backend="jax"`` runs the per-cell reduction as a jit-compiled
    ``vmap``-ed kernel (``"pallas"`` and ``"auto"`` resolve onto it — the
    batch-completion reduction is a segment-min, already one fused device
    kernel, so there is no separate Pallas variant); ``"numpy"`` loops over
    the (few) cells with vectorized reductions.  Each cell is bit-identical
    to ``simulate_maxmin(dist, N, B, n_trials, seed, rates)`` for the numpy
    backend.  ``worker_batches`` optionally overrides the contiguous
    worker->batch grouping per split (rate-aware placements).
    """
    dist_seq = _normalize_dists(dists)
    splits = list(feasible_b) if feasible_b is not None else divisors(n_workers)
    if not splits:
        raise ValueError("no feasible B values")
    wbs = _validate_worker_batches(worker_batches, splits, n_workers)
    if wbs is None:
        for b in splits:
            if n_workers % b:
                raise ValueError(f"B={b} infeasible: must divide N={n_workers}")
    rates_arr = _validate_rates(rates, n_workers)
    backend = resolve_sweep_backend(backend)

    rng = np.random.default_rng(seed)
    unit = rng.standard_exponential((n_trials, n_workers))

    order = _shared_draw_order(dist_seq, unit)
    if backend in ("jax", "pallas"):
        import jax

        loads, wb, valid = _split_arrays(n_workers, splits, wbs)
        # (n_dists, T, N) load-independent cores, same math as the numpy
        # backend (that unification is the empirical/parametric parity
        # contract).  Allocated directly in the device dtype: the cast per
        # entry is identical to the one the jit boundary would apply, and
        # a many-resample sweep does not hold a second full-size f64 copy.
        dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
        cores = np.empty((len(dist_seq), n_trials, n_workers), dtype=dtype)
        for di, d in enumerate(dist_seq):
            cores[di] = _unit_times(unit, d, rates_arr, order=order)
        samples = _sweep_jax(cores, loads, wb, valid,
                             indices_sorted=wbs is None)
    else:
        samples = np.empty((len(dist_seq), len(splits), n_trials))
        for di, dist in enumerate(dist_seq):
            core = _unit_times(unit, dist, rates_arr, order=order)
            for si, b in enumerate(splits):
                times = core * (n_workers / b)
                if wbs is None:
                    r = n_workers // b
                    samples[di, si] = (
                        times.reshape(n_trials, b, r).min(axis=2).max(axis=1)
                    )
                else:
                    samples[di, si] = _group_min_times(
                        times, wbs[si], b).max(axis=1)

    return SweepSimResult(
        n_workers=n_workers,
        splits=tuple(splits),
        dists=dist_seq,
        samples=samples,
        backend=backend,
    )


# ---------------------------------------------------------------------------
# coded-computation sweeps: (scheme, s) cells on the shared CRN draws
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CodedSweepResult:
    """Samples for every (distribution, coding candidate) cell of a sweep.

    ``samples[d, c]`` holds completion (or post-warmup sojourn, for
    :func:`sweep_sojourn_coded`) times for ``dists[d]`` under
    ``candidates[c]``, generated from the SAME unit-exponential draw
    matrix a replication sweep at the same seed consumes — so a coded
    cell is directly comparable to any ``sweep_simulate`` /
    ``sweep_sojourn`` cell (common random numbers across the
    replication-vs-coding race).  Encode+decode overheads are already
    ADDED to every sample.  ``backend`` records the engine that ran.
    """

    n_workers: int
    candidates: tuple[CodingCandidate, ...]
    dists: tuple[ServiceDistribution, ...]
    samples: np.ndarray  # (n_dists, n_candidates, n_trials)
    backend: str

    def result(self, c_index: int, dist_index: int = 0) -> SimResult:
        return SimResult(self.samples[dist_index, c_index])

    def means(self) -> np.ndarray:
        """(n_dists, n_candidates) empirical mean completion times."""
        return self.samples.mean(axis=2)

    def best_mean(self, dist_index: int = 0) -> tuple[CodingCandidate, float]:
        m = self.means()[dist_index]
        c = int(np.argmin(m))
        return self.candidates[c], float(m[c])


def _validate_coding_candidates(
    candidates: Sequence[CodingCandidate], n_workers: int
) -> tuple[CodingCandidate, ...]:
    cands = tuple(candidates)
    if not cands:
        raise ValueError("at least one coding candidate required")
    for c in cands:
        if not isinstance(c, CodingCandidate):
            raise TypeError(
                f"coding candidates must be CodingCandidate, got "
                f"{type(c).__name__}"
            )
        c.k(n_workers)  # raises when s >= N
    return cands


def _coded_cell_stack(
    dist_seq, cands, unit, rates_arr, order, n_workers, dtype, scale=1.0
):
    """(D*C, T, N) load-scaled worker-time cells (c = d*len(cands) + ci),
    plus the per-cell quorum vector — the host-side build both coded
    sweeps share.  A constant-load scalar multiply keeps each cyclic cell
    bit-identical to the legacy ``simulate_gradient_coding`` rewrite
    (same ``_unit_times`` core, same float ops)."""
    n_c = len(cands)
    loads = [scale * c.load(n_workers) for c in cands]
    cells = np.empty(
        (len(dist_seq) * n_c, unit.shape[0], n_workers), dtype=dtype
    )
    for di, dist in enumerate(dist_seq):
        core = _unit_times(unit, dist, rates_arr, order=order)
        for ci, load in enumerate(loads):
            cells[di * n_c + ci] = core * load
    ks = np.tile(
        np.asarray([c.k(n_workers) for c in cands], dtype=np.int32),
        len(dist_seq),
    )
    return cells, ks


def sweep_coded(
    dists: ServiceDistribution | Sequence[ServiceDistribution],
    n_workers: int,
    candidates: Sequence[CodingCandidate],
    n_trials: int = 20_000,
    seed: int = 0,
    rates: Sequence[float] | None = None,
    backend: str = "numpy",
) -> CodedSweepResult:
    """Batch-completion times of every (dist, coding candidate) cell.

    The coded twin of :func:`sweep_simulate`: ONE (n_trials, N) matrix of
    Exp(1) draws — the SAME matrix ``sweep_simulate`` draws at this seed,
    since both consume it first — feeds every cell, so the
    replication-vs-coding comparison is CRN-coupled.  A candidate's cell
    is the ``k``-th order statistic of the N per-worker times at its
    per-worker load (size-dependent service: ``dist.scaled(load)``), plus
    its encode+decode overhead.  The cyclic lane is bit-identical to
    :func:`~repro.core.gradient_coding.simulate_gradient_coding` at the
    same seed (zero overhead, numpy backend).

    ``backend`` routes the order-statistic reduction through the
    :mod:`repro.kernels.sojourn_sweep` coded lanes — numpy reference,
    jit+vmap JAX, or the Pallas kernel (interpreted on CPU only) — recorded on
    the result for :attr:`~repro.core.planner.Plan.backend` provenance.
    """
    from repro.kernels import sojourn_sweep as _ss

    dist_seq = _normalize_dists(dists)
    cands = _validate_coding_candidates(candidates, n_workers)
    rates_arr = _validate_rates(rates, n_workers)
    backend = resolve_sweep_backend(backend)

    rng = np.random.default_rng(seed)
    unit = rng.standard_exponential((n_trials, n_workers))
    order = _shared_draw_order(dist_seq, unit)

    if backend in ("jax", "pallas"):
        import jax

        dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    else:
        dtype = np.float64
    cells, ks = _coded_cell_stack(
        dist_seq, cands, unit, rates_arr, order, n_workers, dtype
    )
    out = _ss.coded_completion_cells(cells, ks, backend=backend)
    samples = np.asarray(out, dtype=float).reshape(
        len(dist_seq), len(cands), n_trials
    )
    overheads = np.asarray([c.total_overhead for c in cands])
    samples = samples + overheads[None, :, None]
    return CodedSweepResult(
        n_workers=n_workers,
        candidates=cands,
        dists=dist_seq,
        samples=samples,
        backend=backend,
    )


def sweep_sojourn_coded(
    dists: ServiceDistribution | Sequence[ServiceDistribution],
    n_workers: int,
    candidates: Sequence[CodingCandidate],
    arrival_rate: float,
    n_jobs: int = 4_000,
    seed: int = 0,
    rates: Sequence[float] | None = None,
    job_load: float = 1.0,
    warmup: int | None = None,
    arrivals: Sequence[float] | None = None,
    backend: str = "numpy",
) -> CodedSweepResult:
    """Sojourn times of coded candidates under the queueing model.

    The load-aware twin of :func:`sweep_coded`, CRN-coupled to
    :func:`sweep_sojourn` at the same seed (identical arrival sequence +
    draw matrix consumption).  A coded job splits its ``job_load`` units
    across ALL N workers — per-worker load ``job_load * load / N`` — and
    the fleet acts as ONE logical FIFO server whose service time is the
    job's k-th worker completion plus encode+decode overhead: coding
    trades replication's across-job parallelism (B parallel replica-sets)
    for within-job parallelism plus straggler diversity, which is exactly
    the Peng/Soljanin/Whiting trade-off the planner must see.  The
    accelerator backends route the order statistic AND the queue
    recursion through the :mod:`repro.kernels.sojourn_sweep` lanes.
    """
    from repro.kernels import sojourn_sweep as _ss

    dist_seq = _normalize_dists(dists)
    cands = _validate_coding_candidates(candidates, n_workers)
    _validate_load(arrival_rate, job_load)
    rates_arr = _validate_rates(rates, n_workers)
    warm = _resolve_warmup(n_jobs, warmup)
    backend = resolve_sweep_backend(backend)

    rng = np.random.default_rng(seed)
    arrivals = _resolve_arrivals(arrivals, n_jobs, arrival_rate, rng)
    unit = rng.standard_exponential((n_jobs, n_workers))
    order = _shared_draw_order(dist_seq, unit)

    overheads = np.asarray([c.total_overhead for c in cands])
    n_c = len(cands)
    if backend != "numpy":
        import jax

        dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
        cells, ks = _coded_cell_stack(
            dist_seq, cands, unit, rates_arr, order, n_workers, dtype,
            scale=job_load / n_workers,
        )
        svc = np.asarray(
            _ss.coded_completion_cells(cells, ks, backend=backend)
        )
        svc = (svc + np.tile(overheads, len(dist_seq))[:, None]).astype(
            dtype
        )[:, :, None]  # (D*C, J, 1): one logical server
        kinds = np.asarray([_ss.KIND_NONE], dtype=np.int32)
        thresholds = np.full((svc.shape[0], 1), np.inf)
        hmasks = np.zeros((1, n_jobs), dtype=bool)
        out, _ = _ss.sojourn_policy_cells(
            arrivals, svc, svc, kinds, thresholds, hmasks,
            np.ones(svc.shape[0], dtype=np.int32), backend=backend,
        )
        samples = np.asarray(out, dtype=float)[:, 0, warm:].reshape(
            len(dist_seq), n_c, n_jobs - warm
        )
    else:
        cells, ks = _coded_cell_stack(
            dist_seq, cands, unit, rates_arr, order, n_workers, np.float64,
            scale=job_load / n_workers,
        )
        svc = _ss.coded_completion_cells(cells, ks, backend="numpy")
        samples = np.empty((len(dist_seq), n_c, n_jobs - warm))
        for di in range(len(dist_seq)):
            for ci in range(n_c):
                col = svc[di * n_c + ci] + overheads[ci]
                samples[di, ci] = _sojourn_recursion(
                    arrivals, col[:, None], 1
                )[warm:]
    return CodedSweepResult(
        n_workers=n_workers,
        candidates=cands,
        dists=dist_seq,
        samples=samples,
        backend=backend,
    )


# ---------------------------------------------------------------------------
# queueing-aware mode: sojourn time under an arrival process
# ---------------------------------------------------------------------------
#
# The serving subsystem factors the fleet into B replica-sets of r = N/B
# groups; first-replica-wins cancellation makes each set ONE logical server
# whose service time is the min over its members' draws.  Under Poisson
# batch-job arrivals the system is an M/G/B queue whose service distribution
# DEPENDS ON B: more batches = more parallel servers but less redundancy per
# server (heavier service tail).  Batch-completion objectives cannot see this
# trade-off — the load-aware planner path scores candidate B by the sojourn
# (queue wait + service) these functions simulate.
#
# Unlike the training sweep, the per-job load here is CONSTANT in B: a
# serving batch is `max_batch_size` requests regardless of how the fleet is
# factored (``job_load`` units of data, default 1).


def _sojourn_recursion(
    arrivals: np.ndarray, svc: np.ndarray, n_groups: int
) -> np.ndarray:
    """FIFO multi-server queue recursion: job i starts on the earliest-free
    replica-set (ties -> lowest index) at max(arrival, free time).

    ``svc[i, g]`` is job i's service time IF dispatched to set g (sets differ
    under heterogeneous rates).  Returns per-job sojourn times.

    The recursion is inherently sequential (each start time depends on all
    earlier dispatches), so it runs as a plain-Python loop over native
    floats — ~10x faster than per-iteration numpy scalars, which matters
    because the online tuner re-runs this sweep during serving.
    """
    free = [0.0] * n_groups
    svc_rows = svc.tolist()
    out = np.empty(len(arrivals))
    for i, a in enumerate(arrivals.tolist()):
        g = min(range(n_groups), key=free.__getitem__)
        start = a if a > free[g] else free[g]
        done = start + svc_rows[i][g]
        free[g] = done
        out[i] = done - a
    return out


def _sojourn_recursion_speculative(
    arrivals: np.ndarray,
    svc: np.ndarray,
    clone_svc: np.ndarray,
    n_groups: int,
    threshold: float,
) -> tuple[np.ndarray, int]:
    """FIFO multi-server queue WITH speculative re-dispatch (event-driven).

    The queueing model of the master's clone-attack rule: jobs dispatch
    FCFS onto the earliest-freed idle replica-set (ties -> lowest index,
    matching :func:`_sojourn_recursion` exactly when no clone fires); a job
    whose first response has not arrived ``threshold`` after its start
    grabs an idle set for ONE clone, drawn from the independent
    ``clone_svc`` matrix.  Crucially, clones only ever take sets that are
    idle AT the trigger instant — and under greedy FCFS dispatch an idle
    set implies an empty queue, so speculation spends spare capacity and
    can never starve queued work (getting this wrong turns speculation
    into a self-inflicted overload at exactly the loads it should help).
    A busy trigger instant RE-ARMS one threshold later (the master's rule),
    and the job completes at the earlier response with both sets busy until
    then (first-replica-wins cancellation).  The model fixes the clone
    budget at ONE per job — the engine's default; larger engine budgets are
    scored by their first clone.

    Returns (per-job sojourns, number of clones launched).
    """
    import heapq as _hq
    import itertools as _it

    svc_rows = svc.tolist()
    clone_rows = clone_svc.tolist()
    n_jobs = len(arrivals)
    out = np.empty(n_jobs)
    free = [0.0] * n_groups  # last time each set freed (dispatch tie-break)
    idle = set(range(n_groups))
    queue: deque[int] = deque()
    # per-job state: start, done, groups held, cloned?, departed?
    start = [0.0] * n_jobs
    done = [0.0] * n_jobs
    held: list[tuple[int, ...]] = [()] * n_jobs
    cloned = [False] * n_jobs
    departed = [False] * n_jobs
    seq = _it.count()
    events: list = []  # (time, seq, kind, job): kind 0=arrive 1=depart 2=spec
    for i, a in enumerate(arrivals.tolist()):
        _hq.heappush(events, (a, next(seq), 0, i))
    n_clones = 0

    def dispatch(i: int, t: float) -> None:
        g = min(idle, key=lambda h: (free[h], h))
        idle.discard(g)
        start[i] = t
        done[i] = t + svc_rows[i][g]
        held[i] = (g,)
        _hq.heappush(events, (done[i], next(seq), 1, i))
        if np.isfinite(threshold):
            _hq.heappush(events, (t + threshold, next(seq), 2, i))

    while events:
        t, _, kind, i = _hq.heappop(events)
        if kind == 0:  # arrival
            if idle:
                dispatch(i, t)
            else:
                queue.append(i)
        elif kind == 1:  # depart (possibly stale after a clone win)
            if departed[i] or done[i] > t:
                continue
            departed[i] = True
            out[i] = done[i] - arrivals[i]
            for g in held[i]:
                free[g] = done[i]
                idle.add(g)
            while queue and idle:
                dispatch(queue.popleft(), t)
        else:  # speculation check
            if departed[i] or done[i] <= t or cloned[i]:
                continue
            if not idle:
                # busy trigger instant: re-arm one threshold later, exactly
                # like the master (done[i] is finite, so this terminates)
                _hq.heappush(events, (t + threshold, next(seq), 2, i))
                continue
            g2 = min(idle, key=lambda h: (free[h], h))
            idle.discard(g2)
            cloned[i] = True
            n_clones += 1
            clone_done = t + clone_rows[i][g2]
            held[i] = (*held[i], g2)
            if clone_done < done[i]:
                done[i] = clone_done
                _hq.heappush(events, (clone_done, next(seq), 1, i))
    return out, n_clones


def _sojourn_recursion_relaunch(
    arrivals: np.ndarray,
    svc: np.ndarray,
    alt_svc: np.ndarray,
    n_groups: int,
    threshold: float,
) -> tuple[np.ndarray, int]:
    """FIFO multi-server queue WITH relaunch-on-straggle (event-driven).

    The queueing model of the master's :class:`~repro.serving.queueing
    .RelaunchPolicy`: a job whose response has not arrived ``threshold``
    after its start CANCELS its in-flight attempt and re-draws a fresh one
    on the SAME replica-set (from the independent ``alt_svc`` matrix) —
    no extra capacity is consumed, so unlike cloning there is no idle-set
    gate and no busy re-arm.  The fresh attempt may finish LATER than the
    cancelled one would have (the gamble relaunch takes); stale depart
    events are skipped by the ``done[i] > t`` guard.  One relaunch per job
    (the engine's default budget).  With ``threshold=inf`` no trigger ever
    fires and the recursion is bit-identical to :func:`_sojourn_recursion`
    (the disabled-settings parity contract).

    Returns (per-job sojourns, number of relaunches).
    """
    import heapq as _hq
    import itertools as _it

    svc_rows = svc.tolist()
    alt_rows = alt_svc.tolist()
    n_jobs = len(arrivals)
    out = np.empty(n_jobs)
    free = [0.0] * n_groups
    idle = set(range(n_groups))
    queue: deque[int] = deque()
    start = [0.0] * n_jobs
    done = [0.0] * n_jobs
    held: list[tuple[int, ...]] = [()] * n_jobs
    relaunched = [False] * n_jobs
    departed = [False] * n_jobs
    seq = _it.count()
    events: list = []  # (time, seq, kind, job): kind 0=arrive 1=depart 2=spec
    for i, a in enumerate(arrivals.tolist()):
        _hq.heappush(events, (a, next(seq), 0, i))
    n_relaunches = 0

    def dispatch(i: int, t: float) -> None:
        g = min(idle, key=lambda h: (free[h], h))
        idle.discard(g)
        start[i] = t
        done[i] = t + svc_rows[i][g]
        held[i] = (g,)
        _hq.heappush(events, (done[i], next(seq), 1, i))
        if np.isfinite(threshold):
            _hq.heappush(events, (t + threshold, next(seq), 2, i))

    while events:
        t, _, kind, i = _hq.heappop(events)
        if kind == 0:  # arrival
            if idle:
                dispatch(i, t)
            else:
                queue.append(i)
        elif kind == 1:  # depart (stale after a relaunch moved completion)
            if departed[i] or done[i] > t:
                continue
            departed[i] = True
            out[i] = done[i] - arrivals[i]
            for g in held[i]:
                free[g] = done[i]
                idle.add(g)
            while queue and idle:
                dispatch(queue.popleft(), t)
        else:  # relaunch check
            if departed[i] or done[i] <= t or relaunched[i]:
                continue
            g = held[i][0]
            relaunched[i] = True
            n_relaunches += 1
            # cancel + fresh draw on the same set; may land later than the
            # cancelled attempt would have
            done[i] = t + alt_rows[i][g]
            _hq.heappush(events, (done[i], next(seq), 1, i))
    return out, n_relaunches


def _sojourn_recursion_hedged(
    arrivals: np.ndarray,
    svc: np.ndarray,
    alt_svc: np.ndarray,
    n_groups: int,
    hedge_fraction: float,
) -> tuple[np.ndarray, int]:
    """FIFO multi-server queue WITH hedged dispatch (event-driven).

    The queueing model of the master's :class:`~repro.serving.queueing
    .HedgedDispatchPolicy` at ``k=2``: a deterministic-stride
    ``hedge_fraction`` of dispatches (the n-th dispatched job is hedged iff
    ``floor((n+1)f) > floor(nf)``, the master's exact rule) grabs ONE
    additional idle replica-set at dispatch time, drawn from the
    independent ``alt_svc`` matrix; both sets race from t=0, the earlier
    response wins, and both free at the winner's time.  Hedges only take
    sets idle at the dispatch instant, so queued work is never displaced.
    With ``hedge_fraction=0`` no job is hedged and the recursion is
    bit-identical to :func:`_sojourn_recursion` (the disabled-settings
    parity contract).

    Returns (per-job sojourns, number of hedges launched).
    """
    import heapq as _hq
    import itertools as _it
    import math as _math

    svc_rows = svc.tolist()
    alt_rows = alt_svc.tolist()
    n_jobs = len(arrivals)
    out = np.empty(n_jobs)
    free = [0.0] * n_groups
    idle = set(range(n_groups))
    queue: deque[int] = deque()
    done = [0.0] * n_jobs
    held: list[tuple[int, ...]] = [()] * n_jobs
    departed = [False] * n_jobs
    seq = _it.count()
    events: list = []  # (time, seq, kind, job): kind 0=arrive 1=depart
    for i, a in enumerate(arrivals.tolist()):
        _hq.heappush(events, (a, next(seq), 0, i))
    n_hedges = 0
    dispatch_count = 0

    def dispatch(i: int, t: float) -> None:
        nonlocal n_hedges, dispatch_count
        g = min(idle, key=lambda h: (free[h], h))
        idle.discard(g)
        done[i] = t + svc_rows[i][g]
        held[i] = (g,)
        n = dispatch_count
        dispatch_count += 1
        hedge = _math.floor((n + 1) * hedge_fraction) > _math.floor(
            n * hedge_fraction
        )
        if hedge and idle:
            g2 = min(idle, key=lambda h: (free[h], h))
            idle.discard(g2)
            n_hedges += 1
            held[i] = (g, g2)
            hedge_done = t + alt_rows[i][g2]
            if hedge_done < done[i]:
                done[i] = hedge_done
        _hq.heappush(events, (done[i], next(seq), 1, i))

    while events:
        t, _, kind, i = _hq.heappop(events)
        if kind == 0:  # arrival
            if idle:
                dispatch(i, t)
            else:
                queue.append(i)
        else:  # depart
            if departed[i]:
                continue
            departed[i] = True
            out[i] = done[i] - arrivals[i]
            for g in held[i]:
                free[g] = done[i]
                idle.add(g)
            while queue and idle:
                dispatch(queue.popleft(), t)
    return out, n_hedges


def _policy_sojourn(
    pol: PolicyCandidate,
    arrivals: np.ndarray,
    svc: np.ndarray,
    alt_svc: np.ndarray | None,
    n_groups: int,
) -> tuple[np.ndarray, int]:
    """Route one policy candidate to its sojourn recursion.

    Returns (per-job sojourns, number of extra interventions — clones,
    relaunches, or hedges).  ``alt_svc`` may be None only for ``'none'``.
    """
    if pol.kind == "none":
        return _sojourn_recursion(arrivals, svc, n_groups), 0
    if pol.kind == "hedged":
        return _sojourn_recursion_hedged(
            arrivals, svc, alt_svc, n_groups, pol.hedge_fraction
        )
    threshold = (
        np.inf if pol.quantile is None else float(np.quantile(svc, pol.quantile))
    )
    if pol.kind == "clone":
        return _sojourn_recursion_speculative(
            arrivals, svc, alt_svc, n_groups, threshold
        )
    return _sojourn_recursion_relaunch(
        arrivals, svc, alt_svc, n_groups, threshold
    )


def _validate_policies(
    policies: Sequence[PolicyCandidate],
) -> tuple[PolicyCandidate, ...]:
    seq = tuple(policies)
    if not seq:
        raise ValueError("at least one policy candidate required")
    for p in seq:
        if not isinstance(p, PolicyCandidate):
            raise TypeError(
                f"policies must be PolicyCandidate instances, got {type(p).__name__}"
            )
    return seq


def _resolve_arrivals(
    arrivals: Sequence[float] | None,
    n_jobs: int,
    arrival_rate: float,
    rng: np.random.Generator,
) -> np.ndarray:
    """The sweep's arrival sequence: the caller's offsets, else Poisson.

    When ``arrivals`` is None the legacy behavior (and the legacy RNG
    consumption: n_jobs exponentials BEFORE the service draws) is kept
    bit-for-bit.  A provided sequence must be 1-D, finite, non-decreasing;
    shorter-than-``n_jobs`` sequences are CYCLED, each lap offset by the
    trace span plus one mean gap (the :class:`~repro.serving.arrivals
    .TraceArrivals` replay rule), so a finite engine trace can drive a
    planner sweep of any length.  No RNG is consumed on this path, so the
    service-draw matrices are identical with and without an override.
    """
    if arrivals is None:
        return np.cumsum(rng.standard_exponential(n_jobs)) / arrival_rate
    arr = np.asarray(arrivals, dtype=float)
    if arr.ndim != 1 or arr.size == 0:
        raise ValueError("arrivals must be a non-empty 1-D sequence")
    if np.any(~np.isfinite(arr)) or np.any(np.diff(arr) < 0):
        raise ValueError("arrivals must be finite and non-decreasing")
    if arr.size < n_jobs:
        span = float(arr[-1] - arr[0])
        lap = span + span / (arr.size - 1) if span > 0 else 1.0
        reps = -(-n_jobs // arr.size)  # ceil
        arr = np.concatenate([arr + k * lap for k in range(reps)])
    return arr[:n_jobs]


def _group_min_times(
    core: np.ndarray, worker_batch: np.ndarray, n_groups: int
) -> np.ndarray:
    """(n_jobs, n_groups) per-set service times: min over member workers."""
    svc = np.empty((core.shape[0], n_groups))
    for g in range(n_groups):
        members = np.flatnonzero(worker_batch == g)
        if members.size == 0:
            raise ValueError(f"replica-set {g} has no workers")
        svc[:, g] = core[:, members].min(axis=1)
    return svc


def _resolve_warmup(n_jobs: int, warmup: int | None) -> int:
    w = n_jobs // 10 if warmup is None else int(warmup)
    if not 0 <= w < n_jobs:
        raise ValueError(f"warmup={w} out of range for n_jobs={n_jobs}")
    return w


def simulate_sojourn(
    dist: ServiceDistribution,
    n_workers: int,
    n_batches: int,
    arrival_rate: float,
    n_jobs: int = 4_000,
    seed: int = 0,
    rates: Sequence[float] | None = None,
    job_load: float = 1.0,
    warmup: int | None = None,
    worker_batch: Sequence[int] | None = None,
    arrivals: Sequence[float] | None = None,
) -> SimResult:
    """Sojourn times of one (B, r) split under Poisson batch-job arrivals.

    ``arrival_rate`` is in batch-jobs per unit time; each job carries
    ``job_load`` units of data served by one replica-set (service = min over
    the set's scaled draws).  ``worker_batch`` optionally supplies the
    worker -> set map (e.g. a rate-aware placement); default is the
    contiguous ``j // r`` grouping.  The first ``warmup`` jobs (default 10%)
    are dropped so the empty-system transient does not dilute the
    steady-state quantiles.  Offered load past capacity is legal — sojourns
    then grow with the horizon, which is exactly the signal that makes an
    unstable B lose the planner's argmin.

    ``arrivals`` overrides the Poisson arrival sequence with explicit
    absolute offsets (e.g. the serving engine's MMPP/trace offsets, cycled
    to ``n_jobs`` — see :func:`_resolve_arrivals`), so the planner scores
    the process the engine actually runs instead of silently assuming
    Poisson.  A straggler policy's sojourns come from
    :func:`simulate_sojourn_policies`, whose ``'none'`` entry is this call.
    """
    samples = simulate_sojourn_policies(
        dist, n_workers, n_batches, arrival_rate, (PolicyCandidate(),),
        n_jobs=n_jobs, seed=seed, rates=rates, job_load=job_load,
        warmup=warmup, worker_batch=worker_batch, arrivals=arrivals,
    )
    return SimResult(samples[0])


def _validate_load(arrival_rate: float, job_load: float) -> None:
    if arrival_rate <= 0 or not np.isfinite(arrival_rate):
        raise ValueError(f"arrival_rate must be positive, got {arrival_rate}")
    if job_load <= 0:
        raise ValueError(f"job_load must be positive, got {job_load}")


def _resolve_sojourn_args(
    n_workers, n_batches, arrival_rate,
    n_jobs, rates, job_load, warmup, worker_batch,
):
    """Validation + worker->set map resolution for the per-B sojourn
    entry point."""
    _validate_load(arrival_rate, job_load)
    if worker_batch is None:
        if n_workers % n_batches:
            raise ValueError(f"B={n_batches} must divide N={n_workers}")
        wb = np.arange(n_workers) // (n_workers // n_batches)
    else:
        wb = np.asarray(worker_batch, dtype=int)
        if wb.shape != (n_workers,):
            raise ValueError(f"worker_batch shape {wb.shape} != ({n_workers},)")
    return wb, _validate_rates(rates, n_workers), _resolve_warmup(n_jobs, warmup)


def sweep_sojourn(
    dists: ServiceDistribution | Sequence[ServiceDistribution],
    n_workers: int,
    arrival_rate: float,
    n_jobs: int = 4_000,
    seed: int = 0,
    feasible_b: Sequence[int] | None = None,
    rates: Sequence[float] | None = None,
    job_load: float = 1.0,
    warmup: int | None = None,
    arrivals: Sequence[float] | None = None,
    backend: str = "numpy",
    mesh=None,
    worker_batches: Sequence[Sequence[int]] | None = None,
) -> SweepSimResult:
    """Sojourn times for ALL feasible (B, r) splits x distributions, batched.

    The queueing twin of :func:`sweep_simulate`: ONE shared arrival sequence
    and ONE shared (n_jobs, N) unit-exponential draw matrix feed every cell
    (common random numbers), so cross-B sojourn comparisons are
    variance-reduced exactly like the batch-completion sweep.  Each cell is
    bit-identical to ``simulate_sojourn(dist, N, B, ...)`` with the default
    contiguous grouping and the same seed.  ``arrivals`` overrides the
    Poisson arrival sequence with explicit offsets (the engine's actual
    MMPP/trace process, cycled to ``n_jobs``).

    ``backend`` selects the cell engine: ``"numpy"`` (default, f64 event
    recursion), ``"jax"``/``"pallas"`` (the accelerator-resident scan
    kernels of :mod:`repro.kernels.sojourn_sweep`, device precision), or
    ``"auto"``.  ``mesh`` optionally shards the cell axis across devices
    on the jax backend; ``worker_batches`` overrides the contiguous
    worker->set grouping per split.
    """
    dist_seq = _normalize_dists(dists)
    splits = list(feasible_b) if feasible_b is not None else divisors(n_workers)
    if not splits:
        raise ValueError("no feasible B values")
    wbs = _validate_worker_batches(worker_batches, splits, n_workers)
    if wbs is None:
        for b in splits:
            if n_workers % b:
                raise ValueError(f"B={b} infeasible: must divide N={n_workers}")
    _validate_load(arrival_rate, job_load)
    rates_arr = _validate_rates(rates, n_workers)
    warm = _resolve_warmup(n_jobs, warmup)
    backend = resolve_sweep_backend(backend)
    arrivals_given = arrivals is not None

    rng = np.random.default_rng(seed)
    arrivals = _resolve_arrivals(arrivals, n_jobs, arrival_rate, rng)
    unit = rng.standard_exponential((n_jobs, n_workers))

    if backend != "numpy":
        cache_key = ("sojourn", seed, n_jobs, n_workers, arrivals_given,
                     tuple(splits), _wb_cache_tag(wbs))
        accel, _ = _sweep_policies_accel(
            dist_seq, splits, (PolicyCandidate("none"),), arrivals, unit,
            None, rates_arr, job_load, n_workers, warm, backend, mesh, wbs,
            cache_key,
        )
        samples = accel[:, :, 0, :]
    else:
        order = _shared_draw_order(dist_seq, unit)
        samples = np.empty((len(dist_seq), len(splits), n_jobs - warm))
        for di, dist in enumerate(dist_seq):
            core = _unit_times(unit, dist, rates_arr, order=order) * job_load
            for si, b in enumerate(splits):
                if wbs is None:
                    r = n_workers // b
                    svc = core.reshape(n_jobs, b, r).min(axis=2)
                else:
                    svc = _group_min_times(core, wbs[si], b)
                samples[di, si] = _sojourn_recursion(arrivals, svc, b)[warm:]
    return SweepSimResult(
        n_workers=n_workers,
        splits=tuple(splits),
        dists=dist_seq,
        samples=samples,
        backend=backend,
    )


def simulate_sojourn_policies(
    dist: ServiceDistribution,
    n_workers: int,
    n_batches: int,
    arrival_rate: float,
    policies: Sequence[PolicyCandidate],
    n_jobs: int = 4_000,
    seed: int = 0,
    rates: Sequence[float] | None = None,
    job_load: float = 1.0,
    warmup: int | None = None,
    worker_batch: Sequence[int] | None = None,
    arrivals: Sequence[float] | None = None,
    backend: str = "numpy",
) -> list[np.ndarray]:
    """Sojourn samples of ONE (B, placement) under several straggler
    policies.

    The per-B companion of :func:`sweep_sojourn_policies` (and the path
    the rate-aware planner uses): every candidate shares one arrival
    sequence + primary draw matrix + — lazily, only when some candidate is
    not ``'none'`` — one alternate draw matrix (the clone/relaunch/hedge
    draws).  A ``'none'`` entry is bit-identical to
    :func:`simulate_sojourn` at the same seed, and so are disabled
    relaunch/hedged candidates (the CRN parity contracts the tests pin).
    ``backend`` selects the cell engine as in
    :func:`sweep_sojourn_policies`; the lazy alternate draw is preserved
    on every backend, so RNG consumption (and hence any later draw from
    the same seed) is backend-independent.
    """
    pol_seq = _validate_policies(policies)
    wb, rates_arr, warm = _resolve_sojourn_args(
        n_workers, n_batches, arrival_rate,
        n_jobs, rates, job_load, warmup, worker_batch,
    )
    backend = resolve_sweep_backend(backend)
    arrivals_given = arrivals is not None
    rng = np.random.default_rng(seed)
    arr = _resolve_arrivals(arrivals, n_jobs, arrival_rate, rng)
    unit = rng.standard_exponential((n_jobs, n_workers))
    if backend != "numpy":
        need_alt = any(pol.kind != "none" for pol in pol_seq)
        alt_unit = (
            rng.standard_exponential((n_jobs, n_workers)) if need_alt else None
        )
        wbs = None if worker_batch is None else (wb,)
        cache_key = ("sojourn", seed, n_jobs, n_workers, arrivals_given,
                     (n_batches,), _wb_cache_tag(wbs))
        samples, _ = _sweep_policies_accel(
            (dist,), [n_batches], pol_seq, arr, unit, alt_unit, rates_arr,
            job_load, n_workers, warm, backend, None, wbs, cache_key,
        )
        return [samples[0, 0, pi] for pi in range(len(pol_seq))]
    core = _unit_times(unit, dist, rates_arr) * job_load
    svc = _group_min_times(core, wb, n_batches)
    alt_svc = None
    out = []
    for pol in pol_seq:
        if alt_svc is None and pol.kind != "none":
            alt_unit = rng.standard_exponential((n_jobs, n_workers))
            alt_core = _unit_times(alt_unit, dist, rates_arr) * job_load
            alt_svc = _group_min_times(alt_core, wb, n_batches)
        sojourn, _ = _policy_sojourn(pol, arr, svc, alt_svc, n_batches)
        out.append(sojourn[warm:])
    return out


@dataclasses.dataclass(frozen=True)
class PolicySweepResult:
    """Sojourn samples for every (distribution, B, policy) cell.

    The policy-portfolio twin of :class:`SweepSimResult`:
    ``samples[d, s, p]`` holds the post-warmup sojourns of ``dists[d]`` at
    ``splits[s]`` batches under ``policies[p]``, all from ONE shared
    arrival sequence + primary draw matrix + alternate draw matrix, so
    (B, policy) comparisons are variance-reduced.
    ``extra_fraction[d, s, p]`` is the fraction of jobs that launched an
    extra intervention (clone, relaunch, or hedge) — the capacity/work
    price of each policy setting.  ``backend`` records the engine that
    actually produced the samples.
    """

    n_workers: int
    splits: tuple[int, ...]
    policies: tuple[PolicyCandidate, ...]
    dists: tuple[ServiceDistribution, ...]
    samples: np.ndarray  # (n_dists, n_splits, n_policies, n_jobs - warmup)
    extra_fraction: np.ndarray  # (n_dists, n_splits, n_policies)
    backend: str = "numpy"

    def result(
        self,
        n_batches: int,
        policy: PolicyCandidate,
        dist_index: int = 0,
    ) -> SimResult:
        return SimResult(
            self.samples[
                dist_index,
                self.splits.index(n_batches),
                self.policies.index(policy),
            ]
        )


def sweep_sojourn_policies(
    dists: ServiceDistribution | Sequence[ServiceDistribution],
    n_workers: int,
    arrival_rate: float,
    policies: Sequence[PolicyCandidate],
    n_jobs: int = 4_000,
    seed: int = 0,
    feasible_b: Sequence[int] | None = None,
    rates: Sequence[float] | None = None,
    job_load: float = 1.0,
    warmup: int | None = None,
    arrivals: Sequence[float] | None = None,
    backend: str = "numpy",
    mesh=None,
    worker_batches: Sequence[Sequence[int]] | None = None,
) -> PolicySweepResult:
    """Sojourns for ALL (B, straggler-policy) pairs x distributions.

    The planner's scoring engine for the adaptive policy portfolio: every
    cell shares ONE arrival sequence, ONE primary draw matrix, and ONE
    alternate draw matrix (common random numbers), so the argmin over
    (B, policy) — clone vs relaunch vs hedged vs none — measures pure
    policy effect, not sampling noise.  Each ``PolicyCandidate('none')``
    cell is bit-identical to the matching :func:`sweep_sojourn` cell at
    the same seed; each ``('clone', q)`` cell is the clone-attack
    recursion (:func:`_sojourn_recursion_speculative`) at that set-service
    quantile; disabled relaunch/hedged candidates match the ``'none'``
    cells bit-for-bit.
    ``arrivals`` overrides the Poisson arrival sequence (see
    :func:`sweep_sojourn`).

    ``backend`` selects the cell engine (``"numpy"`` default; ``"jax"`` /
    ``"pallas"`` run every (dist, B, policy) cell in ONE device dispatch
    through :mod:`repro.kernels.sojourn_sweep`, sharded over ``mesh`` when
    given); ``worker_batches`` overrides the contiguous worker->set
    grouping per split (rate-aware placements).
    """
    dist_seq = _normalize_dists(dists)
    splits = list(feasible_b) if feasible_b is not None else divisors(n_workers)
    if not splits:
        raise ValueError("no feasible B values")
    wbs = _validate_worker_batches(worker_batches, splits, n_workers)
    if wbs is None:
        for b in splits:
            if n_workers % b:
                raise ValueError(f"B={b} infeasible: must divide N={n_workers}")
    pol_seq = _validate_policies(policies)
    _validate_load(arrival_rate, job_load)
    rates_arr = _validate_rates(rates, n_workers)
    warm = _resolve_warmup(n_jobs, warmup)
    backend = resolve_sweep_backend(backend)
    arrivals_given = arrivals is not None

    rng = np.random.default_rng(seed)
    arr = _resolve_arrivals(arrivals, n_jobs, arrival_rate, rng)
    unit = rng.standard_exponential((n_jobs, n_workers))
    alt_unit = rng.standard_exponential((n_jobs, n_workers))

    if backend != "numpy":
        cache_key = ("sojourn", seed, n_jobs, n_workers, arrivals_given,
                     tuple(splits), _wb_cache_tag(wbs))
        samples, extra = _sweep_policies_accel(
            dist_seq, splits, pol_seq, arr, unit, alt_unit, rates_arr,
            job_load, n_workers, warm, backend, mesh, wbs, cache_key,
        )
        return PolicySweepResult(
            n_workers=n_workers,
            splits=tuple(splits),
            policies=pol_seq,
            dists=dist_seq,
            samples=samples,
            extra_fraction=extra,
            backend=backend,
        )

    order = _shared_draw_order(dist_seq, unit)
    alt_order = _shared_draw_order(dist_seq, alt_unit)
    samples = np.empty(
        (len(dist_seq), len(splits), len(pol_seq), n_jobs - warm)
    )
    extra = np.zeros((len(dist_seq), len(splits), len(pol_seq)))
    for di, dist in enumerate(dist_seq):
        core = _unit_times(unit, dist, rates_arr, order=order) * job_load
        alt_core = (
            _unit_times(alt_unit, dist, rates_arr, order=alt_order) * job_load
        )
        for si, b in enumerate(splits):
            if wbs is None:
                r = n_workers // b
                svc = core.reshape(n_jobs, b, r).min(axis=2)
                alt_svc = alt_core.reshape(n_jobs, b, r).min(axis=2)
            else:
                svc = _group_min_times(core, wbs[si], b)
                alt_svc = _group_min_times(alt_core, wbs[si], b)
            for pi, pol in enumerate(pol_seq):
                soj, n_extra = _policy_sojourn(pol, arr, svc, alt_svc, b)
                samples[di, si, pi] = soj[warm:]
                extra[di, si, pi] = n_extra / n_jobs
    return PolicySweepResult(
        n_workers=n_workers,
        splits=tuple(splits),
        policies=pol_seq,
        dists=dist_seq,
        samples=samples,
        extra_fraction=extra,
        backend=backend,
    )


# ---------------------------------------------------------------------------
# accelerator-resident sweep backends (jax / pallas via repro.kernels)
# ---------------------------------------------------------------------------


SWEEP_BACKENDS = ("numpy", "jax", "pallas", "auto")


def resolve_sweep_backend(backend: str) -> str:
    """Resolve a sweep ``backend`` knob to a concrete backend name.

    ``"numpy"`` resolves without touching jax (keeps the default path
    import-light); ``"auto"`` picks ``"jax"`` when an accelerator device is
    visible and ``"numpy"`` otherwise; ``"jax"``/``"pallas"`` pass through.
    """
    if backend == "numpy":
        return "numpy"
    if backend not in SWEEP_BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r} (use one of {SWEEP_BACKENDS})"
        )
    from repro.kernels.sojourn_sweep import resolve_backend

    return resolve_backend(backend)


def _validate_worker_batches(
    worker_batches, splits: Sequence[int], n_workers: int
) -> tuple[np.ndarray, ...] | None:
    """Per-split worker->set maps (rate-aware placements), validated."""
    if worker_batches is None:
        return None
    wbs = tuple(np.asarray(wb, dtype=int) for wb in worker_batches)
    if len(wbs) != len(splits):
        raise ValueError(
            f"worker_batches has {len(wbs)} entries for {len(splits)} splits"
        )
    for wb, b in zip(wbs, splits):
        if wb.shape != (n_workers,):
            raise ValueError(f"worker_batch shape {wb.shape} != ({n_workers},)")
        if wb.min() < 0 or wb.max() >= b:
            raise ValueError(f"worker_batch ids out of range for B={b}")
    return wbs


# Group-min draw cache: the per-split (min, rank-of-min) reduction of a
# shared CRN draw matrix depends only on (seed, shapes, splits, placement),
# NOT on the distributions being swept — and the tuner re-plans on the same
# seed every observation window, so steady-state re-plans skip the argsort
# + argmin over the (n_jobs, N) matrix entirely.
_GROUP_MIN_CACHE: dict = {}
_GROUP_MIN_CACHE_MAX = 4


def _group_min_draws(unit, splits, n_workers, worker_batches, want_rank,
                     cache_key):
    """Per-split group-minimum of the shared draw matrix.

    Returns ``(umin, rankmin)``: ``umin[s, j, g]`` is the minimum draw of
    job j over replica-set g at split ``splits[s]`` (+inf in padded slots)
    and ``rankmin`` its global rank in the flattened matrix (the input to
    empirical quantile coupling; ``None`` unless ``want_rank``).  Because
    every supported service transform is monotone per worker at uniform
    rates, the group-argmin is distribution-independent — computed once and
    cached, it turns each per-distribution cell build into a ``(J, B)``
    gather instead of an ``(J, N)`` materialization.
    """
    ent = _GROUP_MIN_CACHE.get(cache_key)
    if ent is not None and (not want_rank or ent[1] is not None):
        return ent
    n_jobs = unit.shape[0]
    gmax = max(splits)
    umin = np.full((len(splits), n_jobs, gmax), np.inf)
    pos = np.zeros((len(splits), n_jobs, gmax), dtype=np.int64)
    rows = np.arange(n_jobs)[:, None]
    for si, b in enumerate(splits):
        if worker_batches is None:
            r = n_workers // b
            am = unit.reshape(n_jobs, b, r).argmin(axis=2)
            workers = np.arange(b)[None, :] * r + am
        else:
            wb = worker_batches[si]
            workers = np.empty((n_jobs, b), dtype=np.int64)
            for g in range(b):
                members = np.flatnonzero(wb == g)
                if members.size == 0:
                    raise ValueError(f"replica-set {g} has no workers")
                workers[:, g] = members[unit[:, members].argmin(axis=1)]
        umin[si, :, :b] = unit[rows, workers]
        pos[si, :, :b] = rows * n_workers + workers
    rankmin = None
    if want_rank:
        order = np.argsort(unit.ravel(), kind="stable")
        inv = np.empty(order.size, dtype=np.int64)
        inv[order] = np.arange(order.size)
        rankmin = inv[pos.ravel()].reshape(pos.shape)
    if len(_GROUP_MIN_CACHE) >= _GROUP_MIN_CACHE_MAX:
        _GROUP_MIN_CACHE.pop(next(iter(_GROUP_MIN_CACHE)))
    _GROUP_MIN_CACHE[cache_key] = (umin, rankmin)
    return umin, rankmin


def _hist_quantile(atoms: np.ndarray, cum: np.ndarray, q: float) -> float:
    """np.quantile('linear') of the multiset {atoms repeated by counts}.

    ``cum`` is the cumulative count vector; evaluating through the
    histogram makes the per-cell threshold O(n_atoms) instead of
    O(cell) — the difference between sub-second and multi-second
    thresholds at K=256 resamples.
    """
    m = int(cum[-1])
    h = q * (m - 1)
    lo = int(np.floor(h))
    hi = min(lo + 1, m - 1)
    v_lo = atoms[np.searchsorted(cum, lo, side="right")]
    v_hi = atoms[np.searchsorted(cum, hi, side="right")]
    return float(v_lo + (v_hi - v_lo) * (h - lo))


def _policy_cell_tensors(
    dist_seq, splits, pol_seq, unit, alt_unit, rates_arr, job_load,
    n_workers, worker_batches, cache_key,
):
    """Materialize the (cell, job, group) service tensors for the kernels.

    Returns ``(svc, alt, thresholds, n_groups)`` with cells ordered
    ``c = dist_index * len(splits) + split_index``: ``svc``/``alt`` are
    float32 ``(D*S, J, Gmax)`` (``alt`` is None when ``alt_unit`` is),
    ``thresholds`` float64 ``(D*S, P)`` trigger delays (inf = disabled),
    ``n_groups`` int32 ``(D*S,)``.

    At uniform rates each cell is a per-distribution gather on the cached
    group-min draws (values bit-equal to the legacy reshape-min build,
    since all service transforms are monotone); skewed rates break
    worker-axis monotonicity, so that path materializes the full per-dist
    core matrix exactly like the numpy backend.
    """
    n_jobs = unit.shape[0]
    gmax = max(splits)
    n_d, n_s, n_p = len(dist_seq), len(splits), len(pol_seq)
    quantiles = sorted(
        {p.quantile for p in pol_seq
         if p.kind in ("clone", "relaunch") and p.quantile is not None}
    )
    svc = np.zeros((n_d * n_s, n_jobs, gmax), dtype=np.float32)
    alt = np.zeros_like(svc) if alt_unit is not None else None
    thresholds = np.full((n_d * n_s, n_p), np.inf)
    n_groups = np.tile(np.asarray(splits, dtype=np.int32), n_d)

    def _fill_thresholds(c, thr_by_q):
        for pi, p in enumerate(pol_seq):
            if p.kind in ("clone", "relaunch") and p.quantile is not None:
                thresholds[c, pi] = thr_by_q[p.quantile]

    if rates_arr is None:
        has_emp = any(isinstance(d, Empirical) for d in dist_seq)
        umin, rankmin = _group_min_draws(
            unit, splits, n_workers, worker_batches, has_emp,
            cache_key + ("primary",),
        )
        aumin = arank = None
        if alt_unit is not None:
            aumin, arank = _group_min_draws(
                alt_unit, splits, n_workers, worker_batches, has_emp,
                cache_key + ("alt",),
            )
        m_total = n_jobs * n_workers
        # distribution-independent per-split pieces, computed once
        uq = {(si, q): np.quantile(umin[si, :, :b], q)
              for si, b in enumerate(splits) for q in quantiles}
        hists: dict = {}
        idx_cache: dict = {}
        for si, b in enumerate(splits):
            for di, dist in enumerate(dist_seq):
                c = di * n_s + si
                if isinstance(dist, Empirical):
                    n_at = dist.n_atoms
                    if dist.weights is None:
                        if (si, n_at) not in idx_cache:
                            idx_cache[si, n_at] = (
                                (2 * rankmin[si, :, :b] + 1) * n_at
                                // (2 * m_total)
                            )
                        idx = idx_cache[si, n_at]
                        cell = dist._atoms_arr[idx] * job_load
                        if quantiles:
                            if (si, n_at) not in hists:
                                hists[si, n_at] = np.cumsum(np.bincount(
                                    idx.ravel(), minlength=n_at))
                            cum = hists[si, n_at]
                            _fill_thresholds(c, {
                                q: _hist_quantile(dist._atoms_arr, cum, q)
                                * job_load for q in quantiles})
                    else:
                        levels = (2.0 * rankmin[si, :, :b] + 1.0) / (
                            2.0 * m_total)
                        cell = dist.ppf(levels.ravel()).reshape(
                            levels.shape) * job_load
                        _fill_thresholds(c, {
                            q: float(np.quantile(cell, q)) for q in quantiles})
                    svc[c, :, :b] = cell
                    if alt is not None:
                        if dist.weights is None:
                            aidx = ((2 * arank[si, :, :b] + 1) * n_at
                                    // (2 * m_total))
                            alt[c, :, :b] = dist._atoms_arr[aidx] * job_load
                        else:
                            lv = (2.0 * arank[si, :, :b] + 1.0) / (
                                2.0 * m_total)
                            alt[c, :, :b] = dist.ppf(lv.ravel()).reshape(
                                lv.shape) * job_load
                else:
                    shift, mu = _dist_params(dist)
                    svc[c, :, :b] = (shift + umin[si, :, :b] / mu) * job_load
                    if alt is not None:
                        alt[c, :, :b] = (
                            shift + aumin[si, :, :b] / mu) * job_load
                    _fill_thresholds(c, {
                        q: (shift + uq[si, q] / mu) * job_load
                        for q in quantiles})
        return svc, alt, thresholds, n_groups

    # skewed rates: full per-dist core materialization (correctness path)
    order = _shared_draw_order(dist_seq, unit)
    alt_order = (_shared_draw_order(dist_seq, alt_unit)
                 if alt_unit is not None else None)
    for di, dist in enumerate(dist_seq):
        core = _unit_times(unit, dist, rates_arr, order=order) * job_load
        alt_core = (_unit_times(alt_unit, dist, rates_arr, order=alt_order)
                    * job_load if alt_unit is not None else None)
        for si, b in enumerate(splits):
            c = di * n_s + si
            if worker_batches is None:
                r = n_workers // b
                cell = core.reshape(n_jobs, b, r).min(axis=2)
                if alt_core is not None:
                    alt[c, :, :b] = alt_core.reshape(
                        n_jobs, b, r).min(axis=2)
            else:
                cell = _group_min_times(core, worker_batches[si], b)
                if alt_core is not None:
                    alt[c, :, :b] = _group_min_times(
                        alt_core, worker_batches[si], b)
            svc[c, :, :b] = cell
            _fill_thresholds(
                c, {q: float(np.quantile(cell, q)) for q in quantiles})
    return svc, alt, thresholds, n_groups


def _sweep_policies_accel(
    dist_seq, splits, pol_seq, arr, unit, alt_unit, rates_arr, job_load,
    n_workers, warm, backend, mesh, worker_batches, cache_key,
):
    """Run a (dist, B, policy) sweep through the accelerator kernels.

    Returns ``(samples (D, S, P, J-warm) f64, extra_fraction (D, S, P))``.
    """
    from repro.kernels import sojourn_sweep as _ss

    n_jobs = unit.shape[0]
    svc, alt, thresholds, n_groups = _policy_cell_tensors(
        dist_seq, splits, pol_seq, unit, alt_unit, rates_arr, job_load,
        n_workers, worker_batches, cache_key,
    )
    kinds = np.array([_ss.policy_kind_code(p.kind) for p in pol_seq],
                     dtype=np.int32)
    hmasks = np.stack([
        _ss.hedge_mask(n_jobs, p.hedge_fraction if p.kind == "hedged" else 0.0)
        for p in pol_seq
    ])
    n_d, n_s, n_p = len(dist_seq), len(splits), len(pol_seq)
    # Dispatch per (split, trigger-group) instead of one big padded call:
    # cells of a small B then waste no work on another split's group
    # padding, and trigger-free policies (none/hedged) stop paying the
    # clone/relaunch lanes' event-resolution iterations inside the vmapped
    # while_loop (lanes converge together per dispatch).  Per-cell results
    # are bit-identical to the single padded dispatch — padded groups are
    # invalid-masked either way — so this is purely a wall-clock split.
    trig = [i for i, p in enumerate(pol_seq)
            if p.kind in ("clone", "relaunch")]
    plain = [i for i in range(n_p) if i not in trig]
    samples = np.empty((n_d, n_s, n_p, n_jobs), dtype=float)
    extras = np.empty((n_d, n_s, n_p), dtype=float)
    for si in range(n_s):
        cells = slice(si, None, n_s)  # cell order is c = di * n_s + si
        ng_s = n_groups[cells]
        g = int(ng_s.max())
        svc_s = np.ascontiguousarray(svc[cells, :, :g])
        alt_s = (np.ascontiguousarray(alt[cells, :, :g])
                 if alt is not None else svc_s)
        for pidx in (p for p in (plain, trig) if p):
            out, x = _ss.sojourn_policy_cells(
                arr, svc_s, alt_s, kinds[pidx],
                np.ascontiguousarray(thresholds[cells][:, pidx]),
                hmasks[pidx], ng_s, backend=backend, mesh=mesh,
            )
            samples[:, si, pidx, :] = np.asarray(out, dtype=float)
            extras[:, si, pidx] = np.asarray(x, dtype=float)
    return samples[..., warm:], extras / n_jobs


def _wb_cache_tag(worker_batches) -> object:
    if worker_batches is None:
        return None
    return tuple(wb.tobytes() for wb in worker_batches)


# ---------------------------------------------------------------------------
# multi-tenant serving sweep: (B, policy, max_wait, shed) x classes
# ---------------------------------------------------------------------------

# Admission throttle depth for ShedPolicy('cap') formation: a new batch only
# forms while the fluid job backlog is below this many jobs PER replica-set
# (q_max = depth * B), so overload waits in the admission queue — where the
# queue cap and weight-aware eviction can see it — instead of in an
# unbounded formed-batch buffer.
_THROTTLE_DEPTH = 2.0


def _mean_min_service(dist: ServiceDistribution, r: int, job_load: float):
    """Closed-form mean of one replica-set's service (min over ``r``
    replicas) — the drain-rate anchor of the 'cap' admission throttle.

    ``scaled(s) = s*shift + Exp(1)*s/mu`` makes the min over ``r`` i.i.d.
    replicas ``s*shift + Exp(1)*s/(r*mu)``, so the mean is exact for every
    mu-exposing distribution (the only kind the serving sweep accepts).
    """
    shift, mu = _dist_params(dist)
    return (float(shift) + 1.0 / (r * float(mu))) * float(job_load)


def _sample_metric(samples: np.ndarray, metric: str) -> float:
    """Objective metric of a latency sample vector (the serving twin of
    :func:`repro.core.spectrum.metric_value`, which reads precomputed
    spectrum points — same four-literal vocabulary)."""
    s = np.asarray(samples, dtype=float)
    if metric == "mean":
        return float(s.mean())
    if metric == "var":
        return float(s.var(ddof=1)) if s.size > 1 else 0.0
    if metric == "p99":
        return float(np.quantile(s, 0.99))
    if metric == "p999":
        return float(np.quantile(s, 0.999))
    raise ValueError(
        f"unknown metric {metric!r} (expected 'mean'|'var'|'p99'|'p999')"
    )


def _validate_classes(slo_classes) -> tuple[SloClass, ...]:
    classes = tuple(slo_classes)
    if not classes:
        raise ValueError("at least one SloClass is required")
    if not all(isinstance(c, SloClass) for c in classes):
        raise TypeError(f"slo_classes must be SloClass instances: {classes}")
    if len({c.name for c in classes}) != len(classes):
        raise ValueError(f"duplicate class names in {classes}")
    return classes


def _form_schedule(
    arrivals: np.ndarray,
    class_idx: np.ndarray,
    names: Sequence[str],
    weights: np.ndarray,
    batch_size: int,
    max_wait: float,
    shed: ShedPolicy,
    deadlines: np.ndarray,
    drain_rate: float | None = None,
    q_max: float = math.inf,
) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic request->batch formation pre-pass of the serving sweep.

    Replays the event-driven master's admission + formation layer on one
    request trace, WITHOUT service draws — formation is arrival-driven, so
    the job stream it produces is shared by every (dist, B, policy) cell of
    the same (max_wait, shed) combo (the CRN seam the sweep exploits).  The
    model mirrors :class:`repro.serving.queueing.EventDrivenMaster`:

    * WFQ admission: per-class FIFO lanes, stride-scheduled by ``weights``
      (pass += 1/weight per pop; an idle class re-joins at the scheduler's
      virtual time) — one class degenerates to plain FIFO;
    * a batch forms when ``batch_size`` requests wait, or when the OLDEST
      queued request has waited ``max_wait`` (whichever first); leftovers
      flush at the end of the stream;
    * ``shed.kind == 'expired'``: requests past their deadline are shed at
      admission or at the formation boundary;
    * ``shed.kind == 'cap'``: formation is throttled against a fluid drain
      model of the replica-set fabric (``drain_rate`` jobs/time; a batch
      only forms while the fluid backlog is below ``q_max`` jobs — the
      ``max_wait`` timer bypasses the throttle, so the oldest-waiting bound
      still holds), and an arrival finding ``shed.cap`` requests queued is
      shed — or, when it belongs to a strictly heavier class, evicts the
      NEWEST request of the cheapest backlogged class instead.

    Returns ``(formed, req_job)``: ``formed[j]`` is job ``j``'s formation
    time (non-decreasing) and ``req_job[i]`` the job serving request ``i``
    (−1 = shed).
    """
    n_req = len(arrivals)
    req_job = np.full(n_req, -1, dtype=np.int64)
    formed: list[float] = []
    n_classes = len(names)
    lanes: list[deque] = [deque() for _ in range(n_classes)]
    lane_pass = [0.0] * n_classes
    vclock = 0.0
    n_queued = 0
    cap = shed.cap if shed.kind == "cap" else None
    expire = shed.kind == "expired"
    throttled = drain_rate is not None
    vj = 0.0  # fluid job backlog (throttled formation only)
    t_fluid = 0.0

    def drain(t: float) -> None:
        nonlocal vj, t_fluid
        if throttled:
            vj = max(0.0, vj - (t - t_fluid) * drain_rate)
            t_fluid = t

    def oldest() -> float:
        return min(
            (arrivals[ln[0]] for ln in lanes if ln), default=math.inf
        )

    def pop_one() -> int:
        nonlocal vclock, n_queued
        best = best_c = None
        for c in range(n_classes):
            if not lanes[c]:
                continue
            key = (lane_pass[c], arrivals[lanes[c][0]], names[c])
            if best is None or key < best:
                best, best_c = key, c
        i = lanes[best_c].popleft()
        vclock = lane_pass[best_c]
        lane_pass[best_c] += 1.0 / weights[best_c]
        n_queued -= 1
        return i

    def form(k: int, t: float) -> None:
        nonlocal vj
        members = []
        for _ in range(k):
            i = pop_one()
            if expire and deadlines[i] < t:
                continue  # shed at the formation boundary (req_job stays -1)
            members.append(i)
        if not members:
            return  # everything popped was dead work
        j = len(formed)
        for i in members:
            req_job[i] = j
        formed.append(t)
        if throttled:
            vj += 1.0

    def evict_for(i: int) -> bool:
        """Weight-aware cap shedding: evict the NEWEST request of the
        cheapest backlogged class when it weighs strictly less than the
        arrival's class; return whether a slot was freed."""
        nonlocal n_queued
        best = best_c = None
        for c in range(n_classes):
            if not lanes[c]:
                continue
            key = (weights[c], names[c])
            if best is None or key < best:
                best, best_c = key, c
        if best is None or best[0] >= weights[class_idx[i]]:
            return False
        lanes[best_c].pop()  # req_job of the victim stays -1
        n_queued -= 1
        return True

    def next_due(t_now: float) -> tuple[float, bool]:
        """(time, is_size) of the next formation due at or before t_now."""
        t_timer = oldest() + max_wait if n_queued else math.inf
        t_size = math.inf
        if throttled and n_queued >= batch_size:
            t_size = t_fluid + max(0.0, vj - (q_max - 1.0)) / drain_rate
        return (t_size, True) if t_size <= t_timer else (t_timer, False)

    for i in range(n_req):
        t = arrivals[i]
        # fire formations due before this arrival (throttle releases and
        # oldest-waiting max_wait timers, in event order)
        while n_queued:
            tn, is_size = next_due(t)
            if tn > t:
                break
            drain(tn)
            form(batch_size if is_size else min(n_queued, batch_size), tn)
        drain(t)
        if expire and deadlines[i] < t:
            continue  # already expired at admission: never queue dead work
        if cap is not None and n_queued >= cap and not evict_for(i):
            continue  # admission-control shedding: the queue is at capacity
        c = class_idx[i]
        if not lanes[c]:
            # a class (re)activating joins at the current virtual time
            lane_pass[c] = max(lane_pass[c], vclock)
        lanes[c].append(i)
        n_queued += 1
        if n_queued >= batch_size and (not throttled or vj + 1.0 <= q_max):
            form(batch_size, t)
    # end of stream: flush leftovers (timer / throttle-release instants
    # when finite, else in max-batch chunks at the last arrival)
    t_end = float(arrivals[-1]) if n_req else 0.0
    while n_queued:
        tn, is_size = next_due(math.inf)
        if not math.isfinite(tn):
            tn, is_size = max(t_end, t_fluid), False
        drain(tn)
        form(batch_size if is_size else min(n_queued, batch_size), tn)
    return np.asarray(formed, dtype=float), req_job


@dataclasses.dataclass(frozen=True)
class ServingSweepResult:
    """Per-request latencies for every (dist, B, policy, max_wait, shed)
    serving cell under multi-tenant classes.

    The request-level twin of :class:`PolicySweepResult`: every cell shares
    ONE request arrival trace, ONE class labeling, ONE primary draw matrix,
    and ONE alternate draw matrix (common random numbers), so comparisons
    across ALL FIVE axes measure pure configuration effect.  Cells of one
    (max_wait, shed) combo also share the formation pre-pass; a cell's jobs
    draw rows ``[:J]`` of the shared matrices, so cells of different combos
    stay CRN-coupled through the common prefix.

    Ragged storage (``J`` varies per combo): ``formed[d][s][w][h]`` is the
    (J,) job formation times, ``samples[d][s][w][h]`` the (P, J) job
    sojourns, ``req_job[d, s, w, h]`` the request->job map (−1 = shed),
    ``extra_fraction[d, s, p, w, h]`` the per-job straggler-policy work
    price.  Scoring happens request-level: :meth:`request_latency` maps job
    sojourns back onto requests (formation wait + job sojourn; NaN = shed),
    :meth:`class_miss_rates` folds sheds + deadline misses per class, and
    :meth:`weighted_metric` / :meth:`feasible` are what the planner ranks.
    Requests ``< warmup`` are simulated but excluded from scoring.
    """

    n_workers: int
    batch_size: int
    splits: tuple[int, ...]
    policies: tuple[PolicyCandidate, ...]
    max_waits: tuple[float, ...]
    sheds: tuple[ShedPolicy, ...]
    dists: tuple[ServiceDistribution, ...]
    classes: tuple[SloClass, ...]
    request_arrivals: np.ndarray  # (R,)
    request_class: np.ndarray  # (R,) index into classes
    deadlines: np.ndarray  # (R,) ABSOLUTE deadline (inf = none)
    warmup: int
    formed: tuple  # [d][s][w][h] -> (J,) job formation times
    req_job: np.ndarray  # (D, S, W, H, R) job index, -1 = shed
    samples: tuple  # [d][s][w][h] -> (P, J) job sojourns
    extra_fraction: np.ndarray  # (D, S, P, W, H)
    backend: str = "numpy"

    def request_latency(self, di, si, pi, wi, hi) -> np.ndarray:
        """(R,) per-request latency (formation wait + job sojourn) of one
        cell; NaN marks shed requests."""
        rj = self.req_job[di, si, wi, hi]
        lat = np.full(rj.shape, np.nan)
        served = rj >= 0
        jobs = rj[served]
        lat[served] = (
            self.formed[di][si][wi][hi][jobs]
            - self.request_arrivals[served]
            + self.samples[di][si][wi][hi][pi][jobs]
        )
        return lat

    def _post_warm(self) -> np.ndarray:
        mask = np.zeros(len(self.request_arrivals), dtype=bool)
        mask[self.warmup:] = True
        return mask

    def class_shed_fractions(self, di, si, wi, hi) -> np.ndarray:
        """(C,) post-warmup shed fraction per class (policy-independent:
        shedding happens at admission/formation, before any draw)."""
        shed = (self.req_job[di, si, wi, hi] < 0) & self._post_warm()
        out = np.zeros(len(self.classes))
        for ci in range(len(self.classes)):
            sel = (self.request_class == ci) & self._post_warm()
            out[ci] = shed[sel].mean() if sel.any() else 0.0
        return out

    def class_miss_rates(self, di, si, pi, wi, hi) -> np.ndarray:
        """(C,) post-warmup deadline-miss rate per class: shed requests and
        served-past-deadline requests both count; classes without a
        deadline report NaN (no miss concept)."""
        lat = self.request_latency(di, si, pi, wi, hi)
        post = self._post_warm()
        out = np.full(len(self.classes), np.nan)
        for ci, cls in enumerate(self.classes):
            if cls.deadline is None:
                continue
            sel = (self.request_class == ci) & post
            if not sel.any():
                out[ci] = 0.0
                continue
            rel = self.deadlines[sel] - self.request_arrivals[sel]
            miss = np.isnan(lat[sel]) | (lat[sel] > rel)
            out[ci] = miss.mean()
        return out

    def feasible(self, di, si, pi, wi, hi) -> bool:
        """True when every class with a ``miss_target`` meets it."""
        rates = self.class_miss_rates(di, si, pi, wi, hi)
        for ci, cls in enumerate(self.classes):
            if cls.miss_target is not None and rates[ci] > cls.miss_target:
                return False
        return True

    def weighted_metric(self, di, si, pi, wi, hi, metric: str) -> float:
        """Weight-averaged per-class latency metric of one cell, over
        SERVED post-warmup requests (shed requests are priced by
        :meth:`class_miss_rates` / :meth:`feasible`, not here; a class with
        no served sample drops out of the average)."""
        lat = self.request_latency(di, si, pi, wi, hi)
        post = self._post_warm()
        total = value = 0.0
        for ci, cls in enumerate(self.classes):
            sel = (self.request_class == ci) & post & ~np.isnan(lat)
            if not sel.any():
                continue
            value += cls.weight * _sample_metric(lat[sel], metric)
            total += cls.weight
        return value / total if total else math.inf


def _serving_common(
    dists, n_workers, request_rate, batch_size, slo_classes, policies,
    max_waits, sheds, n_requests, seed, job_load, warmup, arrivals,
    class_labels,
):
    """Shared validation + CRN draw block of the serving sweep and its
    standalone companion.  RNG consumption order (the parity contract):
    request arrivals first (unless given), then class labels (unless
    given), then the primary draw matrix, then the alternate matrix —
    always all four, so draws are axis- and backend-independent."""
    dist_seq = _normalize_dists(dists)
    for d in dist_seq:
        if isinstance(d, Empirical):
            raise TypeError(
                "the serving sweep requires mu-exposing distributions "
                "(Exp/SExp); Empirical is not supported on this path"
            )
    classes = _validate_classes(slo_classes)
    pol_seq = _validate_policies(policies)
    _validate_load(request_rate, job_load)
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    mw_seq = tuple(float(w) for w in max_waits)
    if not mw_seq or any(not w > 0 for w in mw_seq):
        raise ValueError(f"max_waits must be positive, got {max_waits}")
    shed_seq = tuple(sheds)
    if not shed_seq or not all(isinstance(s, ShedPolicy) for s in shed_seq):
        raise TypeError(f"sheds must be ShedPolicy instances: {sheds}")
    warm = _resolve_warmup(n_requests, warmup)

    rng = np.random.default_rng(seed)
    arr_req = _resolve_arrivals(arrivals, n_requests, request_rate, rng)
    names = tuple(c.name for c in classes)
    if class_labels is None:
        shares = np.array([c.share for c in classes], dtype=float)
        cum = np.cumsum(shares / shares.sum())
        cls_idx = np.minimum(
            np.searchsorted(cum, rng.random(n_requests), side="right"),
            len(classes) - 1,
        ).astype(np.int64)
    else:
        by_name = {n: i for i, n in enumerate(names)}
        try:
            cls_idx = np.array(
                [by_name[str(c)] for c in class_labels], dtype=np.int64
            )
        except KeyError as e:
            raise ValueError(f"unknown class label {e.args[0]!r}") from None
        if len(cls_idx) != n_requests:
            raise ValueError(
                f"class_labels has {len(cls_idx)} entries for "
                f"{n_requests} requests"
            )
    unit = rng.standard_exponential((n_requests, n_workers))
    alt_unit = rng.standard_exponential((n_requests, n_workers))
    rel = np.array(
        [math.inf if c.deadline is None else c.deadline for c in classes]
    )
    deadlines = arr_req + rel[cls_idx]
    weights = np.array([c.weight for c in classes], dtype=float)
    return (dist_seq, classes, pol_seq, mw_seq, shed_seq, warm, arr_req,
            names, cls_idx, unit, alt_unit, deadlines, weights)


def _serving_formation(
    dist, n_batches, n_workers, batch_size, max_wait, shed, arr_req,
    cls_idx, names, weights, deadlines, job_load, cache,
):
    """Formation for one (dist, B, max_wait, shed) cell, memoized: 'cap'
    sheds throttle against the cell's drain rate (so formation depends on
    (dist, B)); other kinds share one formation per (max_wait, shed)."""
    if shed.kind == "cap":
        r = n_workers // n_batches
        drain = shed.utilization * n_batches / _mean_min_service(
            dist, r, job_load
        )
        q_max = _THROTTLE_DEPTH * n_batches
        key = (max_wait, shed, drain, q_max)
    else:
        drain, q_max = None, math.inf
        key = (max_wait, shed)
    if key not in cache:
        cache[key] = _form_schedule(
            arr_req, cls_idx, names, weights, batch_size, max_wait, shed,
            deadlines, drain, q_max,
        )
    return cache[key]


def sweep_sojourn_serving(
    dists: ServiceDistribution | Sequence[ServiceDistribution],
    n_workers: int,
    request_rate: float,
    batch_size: int,
    slo_classes: Sequence[SloClass],
    policies: Sequence[PolicyCandidate],
    max_waits: Sequence[float] = (math.inf,),
    sheds: Sequence[ShedPolicy] = (ShedPolicy("none"),),
    n_requests: int = 20_000,
    seed: int = 0,
    feasible_b: Sequence[int] | None = None,
    job_load: float = 1.0,
    warmup: int | None = None,
    arrivals: Sequence[float] | None = None,
    class_labels: Sequence[str] | None = None,
    backend: str = "numpy",
    mesh=None,
) -> ServingSweepResult:
    """Request-level latencies for ALL (B, policy, max_wait, shed) serving
    cells x distributions, under multi-tenant SLO classes.

    The multi-tenant scoring engine: one shared request trace (Poisson at
    ``request_rate``, or ``arrivals``/``class_labels`` for trace replay) is
    pushed through the WFQ formation pre-pass per (max_wait, shed) combo
    (:func:`_form_schedule`), and each combo's job stream is evaluated
    through the SAME sojourn cell engines as :func:`sweep_sojourn_policies`
    — ``_policy_sojourn`` on numpy, the :mod:`repro.kernels.sojourn_sweep`
    device kernels on ``"jax"``/``"pallas"`` — slicing rows ``[:J]`` of one
    shared primary + alternate draw matrix (common random numbers across
    every axis).  Each job carries the FULL ``job_load`` (padded-batch
    assumption: a partially-filled batch costs as much as a full one).

    Every cell is bit-identical to :func:`simulate_sojourn_serving` at the
    same seed and matching knobs (the standalone replay the parity tests
    pin), and the no-shed single-class cells reduce to the job-level
    :func:`sweep_sojourn_policies` model with arrival-driven formation.
    """
    (dist_seq, classes, pol_seq, mw_seq, shed_seq, warm, arr_req, names,
     cls_idx, unit, alt_unit, deadlines, weights) = _serving_common(
        dists, n_workers, request_rate, batch_size, slo_classes, policies,
        max_waits, sheds, n_requests, seed, job_load, warmup, arrivals,
        class_labels,
    )
    splits = list(feasible_b) if feasible_b is not None else divisors(n_workers)
    if not splits:
        raise ValueError("no feasible B values")
    for b in splits:
        if n_workers % b:
            raise ValueError(f"B={b} infeasible: must divide N={n_workers}")
    backend = resolve_sweep_backend(backend)
    arrivals_given = arrivals is not None

    n_d, n_s, n_p = len(dist_seq), len(splits), len(pol_seq)
    n_w, n_h = len(mw_seq), len(shed_seq)
    req_job = np.full(
        (n_d, n_s, n_w, n_h, n_requests), -1, dtype=np.int64
    )
    formed_out = [
        [[[None] * n_h for _ in range(n_w)] for _ in range(n_s)]
        for _ in range(n_d)
    ]
    samples_out = [
        [[[None] * n_h for _ in range(n_w)] for _ in range(n_s)]
        for _ in range(n_d)
    ]
    extra = np.zeros((n_d, n_s, n_p, n_w, n_h))
    form_cache: dict = {}

    if backend == "numpy":
        for di, dist in enumerate(dist_seq):
            core = _unit_times(unit, dist, None) * job_load
            alt_core = _unit_times(alt_unit, dist, None) * job_load
            for si, b in enumerate(splits):
                r = n_workers // b
                svc_full = core.reshape(n_requests, b, r).min(axis=2)
                alt_full = alt_core.reshape(n_requests, b, r).min(axis=2)
                for wi, mw in enumerate(mw_seq):
                    for hi, shed in enumerate(shed_seq):
                        formed, rj = _serving_formation(
                            dist, b, n_workers, batch_size, mw, shed,
                            arr_req, cls_idx, names, weights, deadlines,
                            job_load, form_cache,
                        )
                        n_jobs = len(formed)
                        req_job[di, si, wi, hi] = rj
                        formed_out[di][si][wi][hi] = formed
                        cell = np.empty((n_p, n_jobs))
                        for pi, pol in enumerate(pol_seq):
                            if n_jobs == 0:
                                continue
                            soj, n_extra = _policy_sojourn(
                                pol, formed, svc_full[:n_jobs],
                                alt_full[:n_jobs], b,
                            )
                            cell[pi] = soj
                            extra[di, si, pi, wi, hi] = n_extra / n_jobs
                        samples_out[di][si][wi][hi] = cell
    else:
        for wi, mw in enumerate(mw_seq):
            for hi, shed in enumerate(shed_seq):
                if shed.kind == "cap":
                    # throttled formation depends on (dist, B): one kernel
                    # dispatch per cell group
                    groups = [
                        ((di,), (si,))
                        for di in range(n_d) for si in range(n_s)
                    ]
                else:
                    groups = [(tuple(range(n_d)), tuple(range(n_s)))]
                for dis, sis in groups:
                    formed, rj = _serving_formation(
                        dist_seq[dis[0]], splits[sis[0]], n_workers,
                        batch_size, mw, shed, arr_req, cls_idx, names,
                        weights, deadlines, job_load, form_cache,
                    )
                    n_jobs = len(formed)
                    g_dists = tuple(dist_seq[di] for di in dis)
                    g_splits = [splits[si] for si in sis]
                    if n_jobs == 0:
                        smp = np.empty(
                            (len(dis), len(sis), n_p, 0)
                        )
                        xtr = np.zeros((len(dis), len(sis), n_p))
                    else:
                        cache_key = (
                            "serving", seed, n_requests, n_workers,
                            arrivals_given, tuple(g_splits), n_jobs,
                        )
                        smp, xtr = _sweep_policies_accel(
                            g_dists, g_splits, pol_seq, formed,
                            unit[:n_jobs], alt_unit[:n_jobs], None,
                            job_load, n_workers, 0, backend, mesh, None,
                            cache_key,
                        )
                    for gi, di in enumerate(dis):
                        for gj, si in enumerate(sis):
                            req_job[di, si, wi, hi] = rj
                            formed_out[di][si][wi][hi] = formed
                            samples_out[di][si][wi][hi] = np.asarray(
                                smp[gi, gj], dtype=float
                            )
                            extra[di, si, :, wi, hi] = xtr[gi, gj]

    return ServingSweepResult(
        n_workers=n_workers,
        batch_size=batch_size,
        splits=tuple(splits),
        policies=pol_seq,
        max_waits=mw_seq,
        sheds=shed_seq,
        dists=dist_seq,
        classes=classes,
        request_arrivals=arr_req,
        request_class=cls_idx,
        deadlines=deadlines,
        warmup=warm,
        formed=tuple(
            tuple(tuple(tuple(h for h in w) for w in s) for s in d)
            for d in formed_out
        ),
        req_job=req_job,
        samples=tuple(
            tuple(tuple(tuple(h for h in w) for w in s) for s in d)
            for d in samples_out
        ),
        extra_fraction=extra,
        backend=backend,
    )


@dataclasses.dataclass(frozen=True)
class ServingSimResult:
    """Standalone replay of ONE serving cell (see
    :func:`simulate_sojourn_serving`)."""

    latency: np.ndarray  # (R,) request latency, NaN = shed
    shed: np.ndarray  # (R,) bool
    request_class: np.ndarray  # (R,) class index
    formed: np.ndarray  # (J,) job formation times
    req_job: np.ndarray  # (R,) job index, -1 = shed
    job_sojourns: np.ndarray  # (J,)
    extra_fraction: float
    warmup: int


def simulate_sojourn_serving(
    dist: ServiceDistribution,
    n_workers: int,
    n_batches: int,
    request_rate: float,
    batch_size: int,
    slo_classes: Sequence[SloClass],
    policy: PolicyCandidate,
    max_wait: float = math.inf,
    shed: ShedPolicy = ShedPolicy("none"),
    n_requests: int = 20_000,
    seed: int = 0,
    job_load: float = 1.0,
    warmup: int | None = None,
    arrivals: Sequence[float] | None = None,
    class_labels: Sequence[str] | None = None,
) -> ServingSimResult:
    """Standalone replay of ONE (B, policy, max_wait, shed) serving cell.

    The independent-path companion of :func:`sweep_sojourn_serving`: same
    RNG consumption order (request arrivals, class labels, primary matrix,
    alternate matrix — the FULL ``(n_requests, n_workers)`` matrices are
    drawn and the job stream slices rows ``[:J]``), same formation
    pre-pass, same sojourn recursion — so the returned latencies are
    bit-identical to the matching sweep cell at the same seed, the parity
    contract the tests pin.
    """
    (dist_seq, classes, pol_seq, mw_seq, shed_seq, warm, arr_req, names,
     cls_idx, unit, alt_unit, deadlines, weights) = _serving_common(
        dist, n_workers, request_rate, batch_size, slo_classes, (policy,),
        (max_wait,), (shed,), n_requests, seed, job_load, warmup, arrivals,
        class_labels,
    )
    if n_workers % n_batches:
        raise ValueError(
            f"B={n_batches} infeasible: must divide N={n_workers}"
        )
    formed, req_job = _serving_formation(
        dist_seq[0], n_batches, n_workers, batch_size, mw_seq[0],
        shed_seq[0], arr_req, cls_idx, names, weights, deadlines, job_load,
        {},
    )
    n_jobs = len(formed)
    r = n_workers // n_batches
    core = _unit_times(unit, dist_seq[0], None) * job_load
    alt_core = _unit_times(alt_unit, dist_seq[0], None) * job_load
    svc = core.reshape(n_requests, n_batches, r).min(axis=2)[:n_jobs]
    alt_svc = alt_core.reshape(n_requests, n_batches, r).min(axis=2)[:n_jobs]
    if n_jobs:
        soj, n_extra = _policy_sojourn(
            pol_seq[0], formed, svc, alt_svc, n_batches
        )
    else:
        soj, n_extra = np.empty(0), 0
    latency = np.full(n_requests, np.nan)
    served = req_job >= 0
    latency[served] = (
        formed[req_job[served]] - arr_req[served] + soj[req_job[served]]
    )
    return ServingSimResult(
        latency=latency,
        shed=~served,
        request_class=cls_idx,
        formed=formed,
        req_job=req_job,
        job_sojourns=soj,
        extra_fraction=n_extra / n_jobs if n_jobs else 0.0,
        warmup=warm,
    )


# ---------------------------------------------------------------------------
# runtime-facing step-time generator
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """A scheduled fault: worker ``worker`` is dead during steps
    [start_step, end_step)."""

    worker: int
    start_step: int
    end_step: int


class StepTimeSimulator:
    """Per-step service-time generator for the runtime harness.

    Models four straggler phenomena on top of the base distribution:

    * i.i.d. randomness (the paper's model),
    * persistent slow workers (multiplicative slowdown),
    * heterogeneous per-worker base rates (``rates``; worker j's exponential
      part runs at rate ``mu * rates[j]``),
    * transient faults (worker produces no result during the event).

    Returns, per step, an array of service times (np.inf for dead workers).
    """

    def __init__(
        self,
        dist: ServiceDistribution,
        n_workers: int,
        seed: int = 0,
        slow_workers: dict[int, float] | None = None,
        faults: Sequence[FaultEvent] = (),
        rates: Sequence[float] | None = None,
    ):
        self._dist = dist
        self._n = n_workers
        self._rng = np.random.default_rng(seed)
        self._slow = dict(slow_workers or {})
        for w in self._slow:
            if not 0 <= w < n_workers:
                raise ValueError(f"slow worker id {w} out of range")
        self._rates = _validate_rates(rates, n_workers)
        self._faults = list(faults)
        self.step = 0

    def next_step(self, loads: np.ndarray | None = None) -> np.ndarray:
        """Draw one step of per-worker service times.

        ``loads``: units of data per worker (defaults to 1.0 each); service
        scales per the size-dependent model.
        """
        if loads is None:
            loads = np.ones(self._n)
        loads = np.asarray(loads, dtype=float)
        if loads.shape != (self._n,):
            raise ValueError(f"loads shape {loads.shape} != ({self._n},)")
        # iid=True: empirical dists draw independent inverse-ECDF samples per
        # step (the sweep's rank coupling over one N-vector would repeat the
        # same N quantiles forever); parametric dists are unaffected
        unit = self._rng.standard_exponential(self._n)
        times = _times_from_unit(unit, loads, self._dist, self._rates, iid=True)
        for w, factor in self._slow.items():
            times[w] *= factor
        for ev in self._faults:
            if ev.start_step <= self.step < ev.end_step:
                times[ev.worker] = np.inf
        self.step += 1
        return times

    def alive_mask(self) -> np.ndarray:
        mask = np.ones(self._n, dtype=bool)
        for ev in self._faults:
            if ev.start_step <= self.step < ev.end_step:
                mask[ev.worker] = False
        return mask


def censored_observations(
    times: np.ndarray, assignment: Assignment, used: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Per-worker (observed_time, censored) telemetry under the paper's rule.

    When a batch's first replica responds, its remaining replicas are
    CANCELLED — the master never sees their full service times, only that
    they exceeded the batch minimum.  Valid right-censored telemetry
    therefore records unused replicas AT their batch's cancellation time;
    feeding their full would-have-been times as censored lower bounds drags
    a censored MLE's fitted rate down by the censoring fraction.  Dead
    workers (inf) are censored at their batch's cancellation time too (or
    stay inf when the whole batch died — the tuner's observe() handles it).
    """
    times = np.asarray(times, dtype=float)
    used = np.asarray(used, dtype=bool)
    batch_done = np.full(assignment.n_batches, np.inf)
    for w, b in enumerate(assignment.worker_batch):
        t = times[w]
        if np.isfinite(t) and t < batch_done[b]:
            batch_done[b] = t
    cancel = np.array([batch_done[b] for b in assignment.worker_batch])
    return np.minimum(times, cancel), ~used


def completion_from_step_times(
    times: np.ndarray, assignment: Assignment
) -> tuple[float, np.ndarray]:
    """Apply the paper's completion rule to one step of worker times.

    Returns (completion_time, used_mask) where used_mask marks the workers
    whose results the master actually consumed (the fastest replica of each
    batch).  Workers with np.inf (dead) are never used; if a batch has no
    finite replica the completion time is inf (job cannot finish -> the
    elastic layer must re-plan).
    """
    b = assignment.n_batches
    used = np.zeros(assignment.n_workers, dtype=bool)
    batch_done = np.full(b, np.inf)
    for batch in range(b):
        members = [j for j, wb in enumerate(assignment.worker_batch) if wb == batch]
        t = times[members]
        k = int(np.argmin(t))
        if np.isfinite(t[k]):
            batch_done[batch] = t[k]
            used[members[k]] = True
    return float(batch_done.max()), used
