"""Empirical/bootstrap service distributions end-to-end (the PR-5 tentpole).

Four contract groups:

* the :class:`repro.core.Empirical` distribution itself — property-based:
  inverse-CDF sampling reproduces the ECDF (KS distance shrinks with sample
  count), moments/quantiles match the source pool, ``batch_service``
  composition holds, Kaplan-Meier construction handles censoring;
* :class:`repro.core.EmpiricalPlanner` — bootstrap votes, confidence, and
  (slow-marked) statistical recovery of the analytic B* on the Fig. 2
  configurations from raw samples;
* the tuner's goodness-of-fit gate — well-specified Exp telemetry keeps
  the parametric path, heavy-tailed lognormal telemetry (through
  ``StepTimeSimulator``) trips the gate and re-plans empirically, in both
  censored and uncensored regimes;
* exposure — serving engine / make_planner accept the 'empirical' mode.
"""

import math

import numpy as np
import pytest
from _prop import given, settings, st

from repro.core import (
    AnalyticPlanner,
    ClusterSpec,
    Empirical,
    EmpiricalPlanner,
    Exponential,
    Objective,
    PolicyCandidate,
    ReplicationPlan,
    ShiftedExponential,
    SimulatedPlanner,
    StepTimeSimulator,
    StragglerTuner,
    TunerConfig,
    batch_service,
    goodness_of_fit,
    ks_critical,
    ks_statistic,
    make_planner,
)

N = 16
FIG2_DISTS = [
    Exponential(mu=1.0),  # Thm 2: B* = 1
    ShiftedExponential(delta=0.01, mu=1.0),  # near-Exp: diversity
    ShiftedExponential(delta=0.25, mu=1.0),  # interior optimum
    ShiftedExponential(delta=1.0, mu=1.0),  # full parallelism
]


# -- the distribution itself --------------------------------------------------


def test_empirical_sorts_and_validates():
    emp = Empirical((3.0, 1.0, 2.0))
    assert emp.atoms == (1.0, 2.0, 3.0)
    assert emp.quantile(0.0) == 1.0 and emp.quantile(1.0) == 3.0
    with pytest.raises(ValueError):
        Empirical(())
    with pytest.raises(ValueError):
        Empirical((1.0, np.inf))
    with pytest.raises(ValueError):
        Empirical((1.0, -0.5))
    with pytest.raises(ValueError):
        Empirical((1.0, 2.0), weights=(1.0,))
    with pytest.raises(ValueError):
        Empirical((1.0, 2.0), weights=(0.0, 0.0))


def test_empirical_weights_follow_atom_sort():
    emp = Empirical((5.0, 1.0), weights=(3.0, 1.0))
    assert emp.atoms == (1.0, 5.0)
    assert emp.weights == (0.25, 0.75)  # normalized AND reordered with atoms
    assert emp.mean() == pytest.approx(0.25 * 1.0 + 0.75 * 5.0)


@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 10_000), sigma=st.floats(0.2, 1.5))
def test_empirical_moments_and_quantiles_match_pool(seed, sigma):
    rng = np.random.default_rng(seed)
    pool = rng.lognormal(0.0, sigma, 400)
    emp = Empirical(tuple(pool))
    assert emp.mean() == pytest.approx(pool.mean())
    assert emp.var() == pytest.approx(pool.var())
    for q in (0.1, 0.5, 0.9):
        assert emp.quantile(q) == pytest.approx(
            np.quantile(pool, q, method="inverted_cdf")
        )
    # cdf/ppf are a Galois pair on the atoms
    atoms = np.asarray(emp.atoms)
    assert np.array_equal(emp.ppf(emp.cdf(atoms)), atoms)


@settings(deadline=None, max_examples=6)
@given(seed=st.integers(0, 10_000))
def test_empirical_sampling_reproduces_ecdf(seed):
    """Inverse-CDF sampling converges to the source ECDF: the KS distance
    at 16x the sample count is well below the distance at 1x."""
    rng = np.random.default_rng(seed)
    pool = rng.gamma(2.0, 1.5, 300)
    emp = Empirical(tuple(pool))

    def ks(n_draws, draw_seed):
        draws = emp.sample(np.random.default_rng(draw_seed), n_draws)
        grid = np.sort(np.asarray(emp.atoms))
        sample_cdf = np.searchsorted(np.sort(draws), grid, side="right") / n_draws
        return float(np.max(np.abs(sample_cdf - emp.cdf(grid))))

    small, large = ks(200, seed + 1), ks(3_200, seed + 1)
    assert large < small
    assert large < 2.5 * ks_critical(3_200, alpha=0.01)
    # every draw is one of the atoms (it IS an ECDF, not a smoother)
    draws = emp.sample(np.random.default_rng(seed + 2), 500)
    assert np.isin(draws, np.asarray(emp.atoms)).all()


@settings(deadline=None, max_examples=8)
@given(
    n=st.sampled_from([8, 12, 16]),
    b=st.sampled_from([1, 2, 4]),
    seed=st.integers(0, 10_000),
)
def test_batch_service_composition_for_empirical(n, b, seed):
    """batch_service scales an Empirical exactly like the parametric
    families: every atom (and hence every moment/quantile) scales by N/B."""
    rng = np.random.default_rng(seed)
    emp = Empirical(tuple(rng.lognormal(0.0, 0.7, 200)))
    scaled = batch_service(emp, n, b)
    s = n / b
    assert isinstance(scaled, Empirical)
    assert np.allclose(np.asarray(scaled.atoms), s * np.asarray(emp.atoms))
    assert scaled.mean() == pytest.approx(s * emp.mean())
    assert scaled.var() == pytest.approx(s * s * emp.var())
    assert scaled.quantile(0.5) == pytest.approx(s * emp.quantile(0.5))
    # and composes: scaling twice == scaling once by the product
    assert np.allclose(
        np.asarray(scaled.scaled(2.0).atoms),
        np.asarray(emp.scaled(2.0 * s).atoms),
    )


def test_from_censored_uncensored_is_plain_ecdf():
    x = np.array([3.0, 1.0, 2.0, 2.0])
    km = Empirical.from_censored(x)
    assert km.atoms == (1.0, 2.0, 3.0)
    assert km.weights == (0.25, 0.5, 0.25)
    assert km.mean() == pytest.approx(x.mean())


def test_from_censored_kaplan_meier_redistributes_tail_mass():
    # deaths at 1 and 3; censored at 2: its mass must flow to the atom at 3
    # (KM: S(1)=2/3, S(3)=0 -> masses 1/3 and 2/3), NOT sit at 2.
    t = np.array([1.0, 2.0, 3.0])
    c = np.array([False, True, False])
    km = Empirical.from_censored(t, c)
    assert km.atoms == (1.0, 3.0)
    assert km.weights == pytest.approx((1 / 3, 2 / 3))
    # naive ECDF of the recorded times would give mean 2.0; KM is unbiased
    # upward of it because the censored time is a LOWER bound
    assert km.mean() > np.mean(t)


def test_from_censored_recovers_true_distribution():
    """Batch-cancellation censoring (the tuner's regime): the KM ECDF of
    censored-at-the-minimum telemetry tracks the TRUE distribution where a
    naive ECDF of the recorded times is biased low."""
    rng = np.random.default_rng(0)
    dist = Exponential(mu=1.0)
    r = 4
    draws = dist.sample(rng, (2_000, r))
    cancel = draws.min(axis=1, keepdims=True)
    observed = np.minimum(draws, cancel)
    censored = draws > cancel  # everyone but the winner
    km = Empirical.from_censored(observed.ravel(), censored.ravel())
    naive = Empirical(tuple(observed.ravel()))
    # over the range the KM actually estimates, its CDF tracks the truth...
    grid = np.linspace(0.05, np.quantile(draws.ravel(), 0.8), 50)
    assert np.max(np.abs(km.cdf(grid) - dist.cdf(grid))) < 0.05
    # ...where the naive ECDF of recorded times is badly biased high (it
    # mistakes every cancellation time for a completion)
    assert np.max(np.abs(naive.cdf(grid) - dist.cdf(grid))) > 0.3
    assert abs(km.mean() - dist.mean()) < abs(naive.mean() - dist.mean())


def test_from_censored_rejects_degenerate_input():
    with pytest.raises(ValueError):
        Empirical.from_censored(np.array([1.0, 2.0]), np.array([True, True]))
    with pytest.raises(ValueError):
        Empirical.from_censored(np.array([]))


# -- goodness of fit ----------------------------------------------------------


def test_ks_statistic_accepts_own_family_rejects_heavy_tail():
    rng = np.random.default_rng(3)
    n = 1_500
    exp_draws = Exponential(mu=2.0).sample(rng, n)
    fit_ok = goodness_of_fit(exp_draws, Exponential(mu=2.0), alpha=0.01)
    assert not fit_ok.rejected
    lognorm = rng.lognormal(0.0, 1.2, n)
    # best-effort exponential fit of lognormal data still fails KS
    fit_bad = goodness_of_fit(
        lognorm, Exponential(mu=1.0 / lognorm.mean()), alpha=0.01
    )
    assert fit_bad.rejected
    assert fit_bad.statistic > fit_ok.statistic


def test_ks_critical_shrinks_with_n():
    assert ks_critical(100) > ks_critical(400) == pytest.approx(
        ks_critical(100) / 2
    )
    with pytest.raises(ValueError):
        ks_critical(0)
    with pytest.raises(ValueError):
        ks_critical(100, alpha=1.5)


# -- EmpiricalPlanner ---------------------------------------------------------


def test_empirical_planner_votes_and_confidence():
    pool = ShiftedExponential(delta=0.25, mu=1.0).sample(
        np.random.default_rng(0), 3_000
    )
    spec = ClusterSpec(n_workers=N, dist=Empirical(tuple(pool)))
    plan = EmpiricalPlanner(n_trials=4_000, seed=0, n_resamples=12).plan(
        spec, Objective(metric="mean")
    )
    assert plan.planner == "empirical"
    assert 0.0 < plan.confidence <= 1.0
    shares = dict(plan.vote_share)
    assert set(shares) == set(spec.feasible_batches())
    assert sum(shares.values()) == pytest.approx(1.0)
    assert shares[plan.n_batches] == plan.confidence
    # majority rule: no other B out-votes the winner
    assert all(shares[b] <= plan.confidence for b in shares)
    # a clear-cut pool decides firmly
    assert plan.confidence >= 0.5


def test_empirical_planner_accepts_parametric_spec_via_synthetic_pool():
    spec = ClusterSpec(n_workers=N, dist=Exponential(mu=1.0))
    plan = EmpiricalPlanner(
        n_trials=2_000, seed=1, n_resamples=8, pool_size=2_000
    ).plan(spec, Objective(metric="mean"))
    assert plan.n_batches == 1  # Thm 2 through the bootstrap
    assert plan.confidence == 1.0


def test_empirical_planner_load_aware_and_speculative():
    pool = ShiftedExponential(delta=0.5, mu=2.0).sample(
        np.random.default_rng(2), 1_500
    )
    spec = ClusterSpec(n_workers=8, dist=Empirical(tuple(pool)))
    clone = PolicyCandidate("clone", quantile=0.9)
    plan = EmpiricalPlanner(n_trials=800, seed=3, n_resamples=5).plan(
        spec,
        Objective(metric="p99", utilization=0.7, policies=(clone,)),
    )
    assert plan.n_batches in spec.feasible_batches()
    assert plan.policy in (PolicyCandidate(), clone)
    assert plan.vote_share is not None


def test_other_planners_report_no_confidence():
    spec = ClusterSpec(n_workers=N, dist=ShiftedExponential(0.25, 1.0))
    plan = SimulatedPlanner(n_trials=1_000, seed=0).plan(spec)
    assert plan.confidence is None and plan.vote_share is None


def test_analytic_planner_rejects_empirical_dist():
    emp = Empirical(tuple(np.linspace(0.5, 2.0, 50)))
    with pytest.raises(ValueError, match="Exp/SExp only"):
        AnalyticPlanner().plan(ClusterSpec(n_workers=8, dist=emp))


def test_make_planner_empirical_mode():
    p = make_planner("empirical", n_trials=500, seed=7, n_resamples=9)
    assert isinstance(p, EmpiricalPlanner)
    assert p.n_trials == 500 and p.n_resamples == 9
    # heterogeneous composes since the rate-aware bootstrap (PR 8)
    het = make_planner("empirical", heterogeneous=True)
    assert isinstance(het, EmpiricalPlanner) and het.consumes_rates


@pytest.mark.slow
@pytest.mark.parametrize(
    "dist", FIG2_DISTS, ids=["exp", "d.01", "d.25", "d1"]
)
def test_empirical_planner_recovers_analytic_bstar(dist):
    """Statistical recovery on the Fig. 2 configuration: EmpiricalPlanner
    fed raw samples from a known Exp/SExp fleet recovers the closed-form
    B* for the MAJORITY of seeds (nightly `pytest -m slow` job)."""
    analytic = AnalyticPlanner().plan(
        ClusterSpec(n_workers=N, dist=dist), Objective(metric="mean")
    )
    hits = 0
    seeds = range(7)
    for seed in seeds:
        pool = dist.sample(np.random.default_rng(seed), 4_000)
        spec = ClusterSpec(n_workers=N, dist=Empirical(tuple(pool)))
        plan = EmpiricalPlanner(
            n_trials=8_000, seed=seed, n_resamples=15
        ).plan(spec, Objective(metric="mean"))
        hits += plan.n_batches == analytic.n_batches
    assert hits > len(seeds) / 2, (
        f"recovered B*={analytic.n_batches} in only {hits}/{len(seeds)} seeds"
    )


@pytest.mark.slow
def test_empirical_planner_variance_objective_recovers_thm4():
    # Thm 4: variance-optimal B is 1 for both families — the bootstrap
    # majority must agree from raw samples
    pool = ShiftedExponential(delta=0.25, mu=1.0).sample(
        np.random.default_rng(0), 4_000
    )
    spec = ClusterSpec(n_workers=N, dist=Empirical(tuple(pool)))
    plan = EmpiricalPlanner(n_trials=8_000, seed=0, n_resamples=15).plan(
        spec, Objective(metric="var")
    )
    assert plan.n_batches == 1


# -- the tuner's goodness-of-fit gate -----------------------------------------


def _fill_tuner(tuner, times_per_step, censored_per_step=None):
    for i, t in enumerate(times_per_step):
        tuner.observe(
            t, None if censored_per_step is None else censored_per_step[i]
        )


def test_gate_keeps_parametric_path_on_well_specified_telemetry():
    tuner = StragglerTuner(
        ReplicationPlan(n_data=N, n_batches=N),
        TunerConfig(min_samples=64, cooldown_steps=0, gof_alpha=0.01),
    )
    rng = np.random.default_rng(0)
    _fill_tuner(tuner, [Exponential(mu=1.0).sample(rng, N) for _ in range(20)])
    rp = tuner.maybe_replan()
    assert tuner.last_gof is not None and not tuner.last_gof.rejected
    assert tuner.last_plan.planner == "analytic"
    assert rp is not None and rp.new_batches == 1  # Thm 2


def test_gate_trips_on_heavy_tailed_step_time_telemetry():
    """Lognormal service times through StepTimeSimulator: no Exp/SExp fit
    survives KS, so the tuner re-plans through the empirical path."""
    heavy = Empirical(
        tuple(np.random.default_rng(1).lognormal(0.0, 1.2, 8_000))
    )
    sim = StepTimeSimulator(heavy, N, seed=2)
    tuner = StragglerTuner(
        ReplicationPlan(n_data=N, n_batches=N),
        TunerConfig(
            min_samples=64, cooldown_steps=0, gof_alpha=0.01,
            sim_trials=2_000, bootstrap_resamples=8,
        ),
    )
    _fill_tuner(tuner, [sim.next_step() for _ in range(20)])
    tuner.maybe_replan()
    assert tuner.last_gof is not None and tuner.last_gof.rejected
    assert tuner.last_plan.planner == "empirical"
    assert tuner.last_plan.confidence is not None
    assert isinstance(tuner.last_plan.spec.dist, Empirical)


def test_gate_handles_censored_telemetry_both_directions():
    rng = np.random.default_rng(3)
    n_steps, cutoff_q = 64, 0.75

    def censor(draws):
        cut = np.quantile(draws, cutoff_q)
        return np.minimum(draws, cut), draws > cut

    # well-specified: censored Exp telemetry keeps the parametric path
    tuner_ok = StragglerTuner(
        ReplicationPlan(n_data=N, n_batches=N),
        TunerConfig(min_samples=64, cooldown_steps=0, gof_alpha=0.01),
    )
    steps = [censor(Exponential(mu=1.0).sample(rng, N)) for _ in range(n_steps)]
    _fill_tuner(tuner_ok, [t for t, _ in steps], [c for _, c in steps])
    tuner_ok.maybe_replan()
    assert not tuner_ok.last_gof.rejected
    assert tuner_ok.last_plan.planner == "analytic"

    # mis-specified: censored lognormal telemetry still trips the gate
    tuner_bad = StragglerTuner(
        ReplicationPlan(n_data=N, n_batches=N),
        TunerConfig(
            min_samples=64, cooldown_steps=0, gof_alpha=0.01,
            sim_trials=2_000, bootstrap_resamples=8,
        ),
    )
    steps = [censor(rng.lognormal(0.0, 1.5, N)) for _ in range(n_steps)]
    _fill_tuner(tuner_bad, [t for t, _ in steps], [c for _, c in steps])
    tuner_bad.maybe_replan()
    assert tuner_bad.last_gof.rejected
    assert tuner_bad.last_plan.planner == "empirical"
    # the empirical spec is the KM window, and censoring informed it:
    # its atoms are only the UNCENSORED observation values
    x, c = tuner_bad.window_observations()
    assert set(tuner_bad.last_plan.spec.dist.atoms) <= set(x[~c])


def test_gate_off_by_default_and_empirical_primary_mode():
    # gate off: heavy-tailed telemetry still plans parametrically
    heavy_rng = np.random.default_rng(4)
    tuner = StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=8),
        TunerConfig(min_samples=32, cooldown_steps=0),
    )
    _fill_tuner(tuner, [heavy_rng.lognormal(0.0, 1.2, 8) for _ in range(10)])
    tuner.maybe_replan()
    assert tuner.last_gof is None
    assert tuner.last_plan.planner == "analytic"
    # primary empirical mode: never fits a family into the plan at all
    tuner2 = StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=8),
        TunerConfig(
            min_samples=32, cooldown_steps=0, mode="empirical",
            sim_trials=1_000, bootstrap_resamples=6,
        ),
    )
    _fill_tuner(tuner2, [heavy_rng.lognormal(0.0, 1.2, 8) for _ in range(10)])
    tuner2.maybe_replan()
    assert tuner2.last_gof is None  # gate is moot: path is already empirical
    assert tuner2.last_plan.planner == "empirical"
    assert isinstance(tuner2.last_plan.spec.dist, Empirical)


def test_tuner_config_empirical_planner_mapping():
    p = TunerConfig(
        mode="empirical", sim_trials=321, bootstrap_resamples=7
    ).planner()
    assert isinstance(p, EmpiricalPlanner)
    assert p.n_trials == 321 and p.n_resamples == 7


# -- serving-engine exposure --------------------------------------------------


def test_serving_engine_empirical_planner_mode():
    from repro.serving.engine import ReplicatedServingEngine, ServeEngineConfig

    eng = ReplicatedServingEngine(
        ServeEngineConfig(
            n_server_groups=8, n_batches=4, batch_size=2,
            utilization=0.6, tuner=True, planner_mode="empirical",
            gof_alpha=0.05, execute_model=False, metric="p99",
        )
    )
    assert isinstance(eng.planner, EmpiricalPlanner)
    out = eng.run_load(n_requests=192)
    assert out["requests"] == 192
    assert math.isfinite(out["p99_sojourn"])
    assert out["final_B"] in (1, 2, 4, 8)
