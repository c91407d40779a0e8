"""Parity pins for the accelerator-resident sojourn sweep.

Layered contract (see docs/architecture.md, "Sweep backends"):

* **f64 / reference layer** — the scan-formulated
  :func:`repro.kernels.sojourn_sweep.ref.sojourn_cell_reference` must be
  BIT-IDENTICAL to every legacy heap-event recursion in
  ``repro.core.simulator`` (plain/clone/relaunch/hedged) at float64.
* **f32 / device layer** — the numpy reference, the jit+vmap backend and
  the Pallas kernel (interpret mode on CPU) must be bit-identical to each
  other at the SAME dtype; ``shard_map`` over a degenerate one-device
  mesh must not change a single bit.
* **end-to-end layer** — ``sweep_sojourn_policies(backend='jax')`` runs
  the device path at float32, so it is compared to the numpy path at
  distribution level (means/quantiles), not per-sample: rare borderline
  trigger events legitimately land on the other side at f32.

Plus the satellite pins: backend provenance on results/Plan, custom
``worker_batches`` parity, and the tuner's measured-replan-time cooldown
waiver.
"""

import numpy as np
import pytest

from repro.core import simulator as S
from repro.core.order_stats import Empirical, Exponential, ShiftedExponential
from repro.core.planner import PolicyCandidate, make_planner, ClusterSpec, Objective
from repro.core.replication import ReplicationPlan
from repro.core.tuner import StragglerTuner, TunerConfig
from repro.kernels.sojourn_sweep import ref as R
from repro.kernels.sojourn_sweep import ops as O


def _random_cell(rng, j_hi=60, g_hi=6):
    n_jobs = int(rng.integers(5, j_hi))
    n_groups = int(rng.integers(1, g_hi))
    arr = np.cumsum(rng.exponential(1.0 / rng.uniform(0.2, 3.0), n_jobs))
    svc = rng.exponential(1.0, (n_jobs, n_groups)) + rng.uniform(0, 0.5)
    alt = rng.exponential(1.0, (n_jobs, n_groups)) + rng.uniform(0, 0.5)
    thr = float(np.quantile(svc, rng.uniform(0.3, 0.95)))
    return arr, svc, alt, thr, n_groups


def test_reference_bit_matches_legacy_recursions():
    """f64 layer: the scan reference IS the heap simulation, bit for bit,
    for all four policy kinds across randomized cells."""
    rng = np.random.default_rng(0)
    for _ in range(40):
        arr, svc, alt, thr, g = _random_cell(rng)
        n_jobs = arr.size

        out, _ = R.sojourn_cell_reference(arr, svc, alt, R.KIND_NONE,
                                          np.inf, None, g)
        np.testing.assert_array_equal(out, S._sojourn_recursion(arr, svc, g))

        out, x = R.sojourn_cell_reference(arr, svc, alt, R.KIND_CLONE,
                                          thr, None, g)
        leg, leg_x = S._sojourn_recursion_speculative(arr, svc, alt, g, thr)
        np.testing.assert_array_equal(out, leg)
        assert x == leg_x

        out, x = R.sojourn_cell_reference(arr, svc, alt, R.KIND_RELAUNCH,
                                          thr, None, g)
        leg, leg_x = S._sojourn_recursion_relaunch(arr, svc, alt, g, thr)
        np.testing.assert_array_equal(out, leg)
        assert x == leg_x

        frac = float(rng.uniform(0.0, 1.0))
        hm = O.hedge_mask(n_jobs, frac)
        out, x = R.sojourn_cell_reference(arr, svc, alt, R.KIND_HEDGED,
                                          np.inf, hm, g)
        leg, leg_x = S._sojourn_recursion_hedged(arr, svc, alt, g, frac)
        np.testing.assert_array_equal(out, leg)
        assert x == leg_x


@pytest.fixture(scope="module")
def cell_batch():
    """One (cells, policies) batch shared by the device-layer tests."""
    rng = np.random.default_rng(7)
    n_jobs, n_g, n_cells = 40, 4, 3
    arr = np.cumsum(rng.exponential(0.5, n_jobs)).astype(np.float32)
    svc = (rng.exponential(1.0, (n_cells, n_jobs, n_g)) + 0.1).astype(np.float32)
    alt = (rng.exponential(1.0, (n_cells, n_jobs, n_g)) + 0.1).astype(np.float32)
    kinds = np.array([R.KIND_NONE, R.KIND_CLONE, R.KIND_RELAUNCH,
                      R.KIND_HEDGED], np.int32)
    thr = np.full((n_cells, 4), np.inf, np.float32)
    thr[:, 1] = np.quantile(svc.astype(np.float64), 0.8, axis=(1, 2))
    thr[:, 2] = np.quantile(svc.astype(np.float64), 0.9, axis=(1, 2))
    hm = np.stack([O.hedge_mask(n_jobs, f) for f in (0.0, 0.0, 0.0, 0.4)])
    ng = np.array([1, 2, 4], np.int32)
    return arr, svc, alt, kinds, thr, hm, ng


def test_jax_and_pallas_bit_match_reference(cell_batch):
    """f32 layer: same dtype in, identical bits out of all three backends
    (the Pallas kernel runs the SAME jnp body as the vmap path, and both
    must reproduce the numpy reference exactly)."""
    out_np, x_np = O.sojourn_policy_cells(*cell_batch, backend="numpy")
    out_jx, x_jx = O.sojourn_policy_cells(*cell_batch, backend="jax")
    out_pl, x_pl = O.sojourn_policy_cells(*cell_batch, backend="pallas")
    np.testing.assert_array_equal(out_np, np.asarray(out_jx))
    np.testing.assert_array_equal(x_np, np.asarray(x_jx))
    np.testing.assert_array_equal(np.asarray(out_jx), np.asarray(out_pl))
    np.testing.assert_array_equal(np.asarray(x_jx), np.asarray(x_pl))


def test_shard_map_degenerate_mesh_is_bit_identical(cell_batch):
    """shard_map over the trivial one-device CPU mesh (the tier-1 stand-in
    for a real fleet mesh) must not change a single bit vs plain jit."""
    out_jx, x_jx = O.sojourn_policy_cells(*cell_batch, backend="jax")
    mesh = O.cells_mesh()
    out_sm, x_sm = O.sojourn_policy_cells(*cell_batch, backend="jax",
                                          mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out_jx), np.asarray(out_sm))
    np.testing.assert_array_equal(np.asarray(x_jx), np.asarray(x_sm))


def test_resolve_backend_knobs():
    assert S.resolve_sweep_backend("numpy") == "numpy"
    assert S.resolve_sweep_backend("jax") == "jax"
    assert S.resolve_sweep_backend("pallas") == "pallas"
    # CPU-only container: auto falls back to numpy (conftest pins
    # JAX_PLATFORMS=cpu, so this is deterministic in tier-1)
    assert S.resolve_sweep_backend("auto") == "numpy"
    with pytest.raises(ValueError, match="backend"):
        S.resolve_sweep_backend("tpu-maxtext")


# -- end-to-end layer --------------------------------------------------------

_DISTS = [Exponential(1.0), ShiftedExponential(0.3, 1.2)]
_POLS = (PolicyCandidate("none"), PolicyCandidate("clone", 0.85),
         PolicyCandidate("hedged", hedge_fraction=0.3))
_KW = dict(n_workers=12, arrival_rate=0.8, n_jobs=200, seed=3,
           feasible_b=[2, 4])


def _dist_close(a, b, rtol=2e-2):
    """Distribution-level agreement: mean + tail quantiles per cell."""
    np.testing.assert_allclose(a.mean(axis=-1), b.mean(axis=-1), rtol=rtol)
    np.testing.assert_allclose(np.quantile(a, 0.9, axis=-1),
                               np.quantile(b, 0.9, axis=-1), rtol=rtol)


def test_policy_sweep_jax_matches_numpy():
    dists = _DISTS + [Empirical(np.random.default_rng(5).gamma(2.0, 0.5, 800))]
    r_np = S.sweep_sojourn_policies(dists, policies=_POLS, **_KW)
    r_jx = S.sweep_sojourn_policies(dists, policies=_POLS, backend="jax",
                                    **_KW)
    assert r_np.backend == "numpy" and r_jx.backend == "jax"
    _dist_close(r_np.samples, r_jx.samples)
    np.testing.assert_allclose(r_np.extra_fraction, r_jx.extra_fraction,
                               atol=2e-2)


def test_plain_and_speculative_sweep_jax_matches_numpy():
    s_np = S.sweep_sojourn(_DISTS, **_KW)
    s_jx = S.sweep_sojourn(_DISTS, backend="jax", **_KW)
    assert s_np.backend == "numpy" and s_jx.backend == "jax"
    _dist_close(s_np.samples, s_jx.samples)

    clone = (PolicyCandidate(), PolicyCandidate("clone", quantile=0.8))
    q_np = S.sweep_sojourn_policies(_DISTS, policies=clone, **_KW)
    q_jx = S.sweep_sojourn_policies(_DISTS, policies=clone, backend="jax",
                                    **_KW)
    _dist_close(q_np.samples, q_jx.samples)
    np.testing.assert_allclose(q_np.extra_fraction, q_jx.extra_fraction,
                               atol=2e-2)


def test_skewed_rates_policy_sweep_jax_matches_numpy():
    rates = np.linspace(0.5, 1.5, 12)
    r_np = S.sweep_sojourn_policies(_DISTS, policies=_POLS, rates=rates,
                                    **_KW)
    r_jx = S.sweep_sojourn_policies(_DISTS, policies=_POLS, rates=rates,
                                    backend="jax", **_KW)
    _dist_close(r_np.samples, r_jx.samples)


def test_worker_batches_thread_through_both_backends():
    """Custom placements (rate-aware assignments) reach the sweep on every
    backend; numpy vs jax agree on the batch-completion sweep exactly."""
    rng = np.random.default_rng(1)
    wbs = [rng.permutation(np.arange(12) % b) for b in (2, 4)]
    u_np = S.sweep_simulate(_DISTS, 12, n_trials=400, seed=1,
                            feasible_b=[2, 4], worker_batches=wbs)
    u_jx = S.sweep_simulate(_DISTS, 12, n_trials=400, seed=1,
                            feasible_b=[2, 4], worker_batches=wbs,
                            backend="jax")
    assert u_np.backend == "numpy" and u_jx.backend == "jax"
    np.testing.assert_allclose(u_np.samples, u_jx.samples, rtol=1e-5)

    with pytest.raises(ValueError, match="worker_batches"):
        S.sweep_simulate(_DISTS, 12, n_trials=50, feasible_b=[2, 4],
                         worker_batches=[np.zeros(12, int)])  # one per B


def test_backend_provenance_reaches_plan():
    """SweepSimResult/Plan record the RESOLVED engine, not the request —
    the PR-8 provenance fix ('auto' never leaks into results)."""
    res = S.sweep_simulate(_DISTS, 12, n_trials=200, feasible_b=[2, 4],
                           backend="auto")
    assert res.backend == "numpy"  # resolved on this CPU-only host

    spec = ClusterSpec(n_workers=12, dist=Exponential(1.0))
    obj = Objective(metric="mean")
    plan = make_planner("simulate", n_trials=500, backend="numpy").plan(
        spec, obj)
    assert plan.backend == "numpy"
    assert make_planner("analytic").plan(spec, obj).backend is None


# -- coded completion cells (PR 9) --------------------------------------------


@pytest.fixture(scope="module")
def coded_cell_batch():
    """(cells, trials, workers) service times + per-cell quorum sizes."""
    rng = np.random.default_rng(13)
    times = (rng.exponential(1.0, (5, 64, 12)) + 0.05).astype(np.float32)
    ks = np.array([1, 4, 8, 12, 6], np.int32)
    return times, ks


def test_coded_cells_all_backends_bit_match(coded_cell_batch):
    """f32 layer: the k-th-order-statistic selection is value-exact, so
    numpy reference, jit+vmap and the Pallas kernel must agree bit for bit
    at the same dtype."""
    times, ks = coded_cell_batch
    out_np = O.coded_completion_cells(times, ks, backend="numpy")
    out_jx = O.coded_completion_cells(times, ks, backend="jax")
    out_pl = O.coded_completion_cells(times, ks, backend="pallas")
    np.testing.assert_array_equal(out_np, np.asarray(out_jx))
    np.testing.assert_array_equal(np.asarray(out_jx), np.asarray(out_pl))


def test_coded_cells_match_sorted_selection(coded_cell_batch):
    """The reference IS the k-th smallest of each trial's worker times."""
    times, ks = coded_cell_batch
    out = O.coded_completion_cells(times, ks, backend="numpy")
    srt = np.sort(times, axis=-1)
    for c, k in enumerate(ks):
        np.testing.assert_array_equal(out[c], srt[c, :, int(k) - 1])


def test_sweep_coded_backends_agree_and_record_provenance():
    """End-to-end coded sweep: jax and pallas produce IDENTICAL samples
    (same traced body), numpy agrees to f32 tolerance, and each result
    carries the engine that actually ran."""
    from repro.core import CodingCandidate

    cands = (
        CodingCandidate("cyclic", 2, encode_overhead=0.0,
                        decode_overhead=0.0),
        CodingCandidate("mds", 6, encode_overhead=0.01,
                        decode_overhead=0.02),
    )
    kw = dict(n_trials=600, seed=5)
    r_np = S.sweep_coded(_DISTS, 12, cands, **kw)
    r_jx = S.sweep_coded(_DISTS, 12, cands, backend="jax", **kw)
    r_pl = S.sweep_coded(_DISTS, 12, cands, backend="pallas", **kw)
    assert (r_np.backend, r_jx.backend, r_pl.backend) == (
        "numpy", "jax", "pallas")
    np.testing.assert_array_equal(r_jx.samples, r_pl.samples)
    np.testing.assert_allclose(r_np.samples, r_jx.samples, rtol=1e-5)
    # measured overhead is ADDED to every sample of its candidate's cells
    zero = S.sweep_coded(_DISTS, 12, (cands[1].__class__(
        "mds", 6, encode_overhead=0.0, decode_overhead=0.0),), **kw)
    np.testing.assert_allclose(
        r_np.samples[:, 1], zero.samples[:, 0] + 0.03, rtol=1e-12)


def test_sweep_sojourn_coded_jax_matches_numpy():
    """Queueing-aware coded sweep shares the replication sweep's layered
    contract: numpy vs accelerated agree at distribution level."""
    from repro.core import CodingCandidate

    cands = (CodingCandidate("cyclic", 3, encode_overhead=0.0,
                             decode_overhead=0.0),
             CodingCandidate("mds", 8, encode_overhead=0.0,
                             decode_overhead=0.0))
    kw = dict(arrival_rate=0.6, n_jobs=300, seed=2)
    r_np = S.sweep_sojourn_coded(_DISTS, 12, cands, **kw)
    r_jx = S.sweep_sojourn_coded(_DISTS, 12, cands, backend="jax", **kw)
    assert r_np.backend == "numpy" and r_jx.backend == "jax"
    _dist_close(r_np.samples, r_jx.samples)


def test_tuner_replan_budget_waives_cooldown():
    """With replan_time_budget set and the measured plan() time under it,
    attempt pacing stops gating re-plans; the budget-less twin still
    backs off for the full cooldown."""
    def make(budget):
        cfg = TunerConfig(window_steps=50, min_samples=16,
                          cooldown_steps=1000, replan_time_budget=budget)
        return StragglerTuner(ReplicationPlan(n_data=8, n_batches=2), cfg)

    rng = np.random.default_rng(0)
    waived, paced = make(budget=60.0), make(budget=None)
    for _ in range(4):
        obs = rng.exponential(1.0, 8)
        waived.observe(obs)
        paced.observe(obs)
    waived.maybe_replan()
    paced.maybe_replan()
    assert waived.last_replan_seconds is not None
    assert waived.last_replan_seconds < 60.0
    first_attempt = waived._last_attempt
    obs = rng.exponential(1.0, 8)
    waived.observe(obs)
    paced.observe(obs)
    waived.maybe_replan()
    paced.maybe_replan()
    assert waived._last_attempt > first_attempt  # pacing waived: re-evaluated
    assert paced._last_attempt == first_attempt  # legacy cooldown still holds
