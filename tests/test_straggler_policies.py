"""Straggler-policy portfolio: relaunch / hedged master semantics, CRN
parity of the policy sweep, arrivals-override and skewed-rates bugfix
regressions, planner portfolio decisions, and online policy adoption."""

import dataclasses
import math

import numpy as np
import pytest

from repro.core import (
    ClusterSpec,
    EmpiricalPlanner,
    Exponential,
    Objective,
    PolicyCandidate,
    ReplicationPlan,
    RescalePlan,
    ShiftedExponential,
    SimulatedPlanner,
    StragglerTuner,
    TunerConfig,
    simulate_sojourn,
    simulate_sojourn_policies,
    straggler_portfolio,
    sweep_sojourn,
    sweep_sojourn_policies,
)
from repro.core.simulator import _sojourn_recursion_speculative
from repro.serving import (
    ClonePolicy,
    EventDrivenMaster,
    HedgedDispatchPolicy,
    MMPPArrivals,
    NoOpPolicy,
    QueuePolicy,
    RelaunchPolicy,
    ReplicatedServingEngine,
    Request,
    ServeEngineConfig,
)

N_FLEET = 16
FLEET_DIST = ShiftedExponential(delta=0.02, mu=2.0)


# -- PolicyCandidate ----------------------------------------------------------

def test_policy_candidate_validation():
    assert not PolicyCandidate().enabled
    assert not PolicyCandidate("clone", quantile=None).enabled
    assert not PolicyCandidate("hedged", hedge_fraction=0.0).enabled
    assert PolicyCandidate("relaunch", quantile=0.9).enabled
    assert PolicyCandidate("hedged", hedge_fraction=0.5).enabled
    with pytest.raises(ValueError):
        PolicyCandidate("warp")
    with pytest.raises(ValueError):
        PolicyCandidate("hedged", quantile=0.9)  # trigger is clone/relaunch
    with pytest.raises(ValueError):
        PolicyCandidate("clone", quantile=1.5)
    with pytest.raises(ValueError):
        PolicyCandidate("clone", quantile=0.9, hedge_fraction=0.5)


def test_objective_policy_portfolio_validation():
    pols = (PolicyCandidate("clone", quantile=0.9),)
    with pytest.raises(ValueError):
        Objective(policies=pols)  # needs load
    with pytest.raises(ValueError):
        Objective(utilization=0.5, policies=())
    ok = Objective(utilization=0.5, policies=pols)
    # a plain-replication baseline always rides the portfolio
    assert ok.policies[0] == PolicyCandidate()
    assert ok.policies[1:] == pols


# -- master semantics: relaunch -----------------------------------------------

def test_relaunch_cancels_and_redraws_on_same_set():
    """A late attempt is cancelled and redrawn fresh on the SAME set; the
    discarded draw is kept for censored telemetry."""
    svc = iter([np.array([10.0]), np.array([1.0])])
    master = EventDrivenMaster(
        1, lambda job, g: next(svc),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=RelaunchPolicy(
            max_relaunches=1, threshold=lambda job: 2.0
        ),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    job = jobs[0]
    assert master.relaunches == 1 and master.speculations == 0
    assert job.n_relaunches == 1 and job.n_clones == 0
    assert job.relaunched_at == [2.0]  # trigger at dispatch + threshold
    assert job.completed == pytest.approx(3.0)  # 2.0 + fresh draw 1.0
    assert job.attempt_dispatched == pytest.approx(2.0)
    assert job.attempt_service == pytest.approx(1.0)
    assert [list(t) for t in job.discarded_service_times] == [[10.0]]
    assert job.groups == [0]  # same set, no extra capacity taken


def test_relaunch_can_move_completion_later():
    """Unlike cloning, relaunch abandons the original draw — a fresh draw
    slower than the remaining original work makes the job finish LATER (the
    stale depart event from the discarded attempt must not complete it)."""
    svc = iter([np.array([3.0]), np.array([5.0])])
    master = EventDrivenMaster(
        1, lambda job, g: next(svc),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=RelaunchPolicy(
            max_relaunches=1, threshold=lambda job: 2.0
        ),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    assert jobs[0].completed == pytest.approx(7.0)  # 2.0 + 5.0, not 3.0


def test_relaunch_budget_exhausted():
    master = EventDrivenMaster(
        1, lambda job, g: np.array([100.0]),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=RelaunchPolicy(
            max_relaunches=2, threshold=lambda job: 1.0
        ),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    assert jobs[0].n_relaunches == 2
    assert master.relaunches == 2


# -- master semantics: hedged dispatch ----------------------------------------

def test_hedged_dispatch_fraction_stride_and_win():
    """hedge_fraction=0.5 hedges every second dispatched job (deterministic
    stride floor((n+1)f) > floor(nf), so job 1 is the first hedged); the
    hedge replica set's faster draw wins and both sets free at the
    winner's completion."""
    draws = iter([
        np.array([2.0]),  # job 0 primary (stride skips job 0)
        np.array([5.0]),  # job 1 primary
        np.array([1.0]),  # job 1 hedge — wins
    ])
    master = EventDrivenMaster(
        2, lambda job, g: next(draws),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=HedgedDispatchPolicy(k=2, hedge_fraction=0.5),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    master.submit(Request(request_id=1, arrival=10.0))
    jobs = master.run()
    assert master.hedges == 1
    assert jobs[0].n_clones == 0
    assert jobs[0].completed == pytest.approx(2.0)
    assert jobs[1].n_clones == 1 and jobs[1].winner_clone == 0
    assert jobs[1].clone_dispatched == [10.0]  # hedges launch AT dispatch
    assert jobs[1].completed == pytest.approx(11.0)


def test_hedged_dispatch_needs_idle_capacity():
    """With every set busy there is nothing to hedge onto: the job runs
    unhedged rather than waiting for capacity."""
    master = EventDrivenMaster(
        1, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=HedgedDispatchPolicy(k=2, hedge_fraction=1.0),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    assert master.hedges == 0
    assert jobs[0].n_clones == 0


def test_noop_policy_matches_no_policy():
    def sampler_factory():
        rng = np.random.default_rng(7)
        return lambda job, g: rng.exponential(0.4, 2)

    outs = []
    for pol in (None, NoOpPolicy()):
        master = EventDrivenMaster(
            4, sampler_factory(),
            policy=QueuePolicy(max_batch_size=1),
            straggler_policy=pol,
        )
        rng = np.random.default_rng(3)
        for i, a in enumerate(np.cumsum(rng.exponential(0.3, 40))):
            master.submit(Request(request_id=i, arrival=float(a)))
        jobs = master.run()
        outs.append([j.completed for j in jobs])
    assert outs[0] == outs[1]


# -- CRN parity of the policy sweep -------------------------------------------

def test_disabled_policies_bit_identical_to_plain_sweep():
    """Every disabled candidate — 'none', a trigger-less relaunch, a
    zero-fraction hedge — must reproduce the plain sojourn sweep draw for
    draw (same CRN matrix, no stray RNG consumption)."""
    policies = (
        PolicyCandidate(),
        PolicyCandidate("relaunch", quantile=None),
        PolicyCandidate("hedged", hedge_fraction=0.0),
    )
    res = sweep_sojourn_policies(
        FLEET_DIST, N_FLEET, arrival_rate=8.0, policies=policies,
        n_jobs=1_200, seed=5,
    )
    plain = sweep_sojourn(
        FLEET_DIST, N_FLEET, arrival_rate=8.0, n_jobs=1_200, seed=5,
    )
    for s in range(len(res.splits)):
        for p in range(len(policies)):
            np.testing.assert_array_equal(
                res.samples[0, s, p], plain.samples[0, s]
            )


def test_clone_policy_cell_bit_identical_to_speculative_sweep():
    """A clone cell is the clone-attack recursion run on the sweep's draw
    order (arrivals, primary matrix, clone matrix), triggered at the
    set-service quantile, bit for bit."""
    policies = (PolicyCandidate("clone", quantile=0.9),)
    n_jobs, rate = 1_200, 8.0
    res = sweep_sojourn_policies(
        FLEET_DIST, N_FLEET, arrival_rate=rate, policies=policies,
        n_jobs=n_jobs, seed=5,
    )
    rng = np.random.default_rng(5)
    arrivals = np.cumsum(rng.standard_exponential(n_jobs)) / rate
    core = FLEET_DIST.delta + rng.standard_exponential(
        (n_jobs, N_FLEET)
    ) / FLEET_DIST.mu
    clone_core = FLEET_DIST.delta + rng.standard_exponential(
        (n_jobs, N_FLEET)
    ) / FLEET_DIST.mu
    pi = res.policies.index(policies[0])
    for s, b in enumerate(res.splits):
        r = N_FLEET // b
        svc = core.reshape(n_jobs, b, r).min(axis=2)
        clone_svc = clone_core.reshape(n_jobs, b, r).min(axis=2)
        sojourn, _ = _sojourn_recursion_speculative(
            arrivals, svc, clone_svc, b, float(np.quantile(svc, 0.9))
        )
        np.testing.assert_array_equal(
            res.samples[0, s, pi], sojourn[n_jobs // 10:]
        )


@pytest.mark.parametrize("policies", [
    (
        PolicyCandidate("relaunch", quantile=0.9),
        PolicyCandidate("hedged", hedge_fraction=0.3),
    ),
    (PolicyCandidate(), PolicyCandidate("clone", quantile=0.9)),
], ids=["relaunch_hedged", "none_clone"])
def test_policy_sweep_cells_match_single_sim(policies):
    """CRN contract: every (B, policy) cell of the batched sweep is
    bit-identical to the per-B call with the same candidates and seed."""
    res = sweep_sojourn_policies(
        FLEET_DIST, N_FLEET, arrival_rate=8.0, policies=policies,
        n_jobs=1_000, seed=4, feasible_b=(2, 4),
    )
    for s, b in enumerate(res.splits):
        single = simulate_sojourn_policies(
            FLEET_DIST, N_FLEET, b, arrival_rate=8.0, policies=policies,
            n_jobs=1_000, seed=4,
        )
        for p in range(len(res.policies)):
            np.testing.assert_array_equal(res.samples[0, s, p], single[p])


# -- queueing master vs recursion agreement (per policy) ----------------------

@pytest.mark.parametrize("candidate", [
    PolicyCandidate(),
    PolicyCandidate("clone", quantile=0.9),
    PolicyCandidate("relaunch", quantile=0.9),
    PolicyCandidate("hedged", hedge_fraction=0.3),
])
def test_master_agrees_with_recursion_per_policy(candidate):
    """The event-driven master and the batched recursion implement the same
    semantics per policy: identical fleet, load and trigger rule must land
    on statistically indistinguishable mean sojourns (different RNG
    streams, so tolerance not bit-equality)."""
    n_groups, rate, n_jobs = 4, 4.0, 6_000
    b_dist = FLEET_DIST  # per-replica batch service (B=4, r=4 of 16)
    sim = simulate_sojourn_policies(
        b_dist, n_groups, n_groups, arrival_rate=rate,
        policies=(candidate,), n_jobs=n_jobs, seed=11,
    )[0]

    threshold = (
        float(np.quantile(b_dist.sample(np.random.default_rng(1), 200_000),
                          candidate.quantile))
        if candidate.quantile is not None
        else math.inf
    )
    if candidate.kind == "clone":
        pol = ClonePolicy(max_clones=1, threshold=lambda job: threshold)
    elif candidate.kind == "relaunch":
        pol = RelaunchPolicy(max_relaunches=1, threshold=lambda job: threshold)
    elif candidate.kind == "hedged":
        pol = HedgedDispatchPolicy(
            k=2, hedge_fraction=candidate.hedge_fraction
        )
    else:
        pol = None
    svc_rng = np.random.default_rng(21)
    master = EventDrivenMaster(
        n_groups, lambda job, g: svc_rng.exponential(1 / b_dist.mu, 1)
        + b_dist.delta,
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=pol,
    )
    arr_rng = np.random.default_rng(31)
    arrivals = np.cumsum(arr_rng.exponential(1 / rate, n_jobs))
    for i, a in enumerate(arrivals):
        master.submit(Request(request_id=i, arrival=float(a)))
    jobs = master.run()
    measured = np.array([j.completed - j.requests[0].arrival for j in jobs])
    warm = n_jobs // 10
    assert np.mean(measured[warm:]) == pytest.approx(
        np.mean(sim), rel=0.12
    )


# -- bugfix regressions -------------------------------------------------------

def test_empirical_planner_rate_aware_bootstrap():
    """EmpiricalPlanner consumes rate skew directly (PR 8): the bootstrap
    sweep couples each resample to the shared draws divided by per-worker
    rates and scores every B under the rate-aware placement the plan
    emits, and so does a clone trigger on the policy axis."""
    spec = ClusterSpec(
        n_workers=8, dist=Exponential(mu=2.0),
        rates=tuple(np.linspace(0.5, 1.5, 8)),
    )
    assert spec.has_skewed_rates
    planner = EmpiricalPlanner(n_trials=400, seed=0, n_resamples=2)
    plan = planner.plan(spec, Objective(metric="mean"))
    assert plan.n_batches in (1, 2, 4, 8)
    assert len(plan.assignment.worker_batch) == 8
    # skew actually reaches the scoring: a uniform twin scores differently
    uniform = dataclasses.replace(spec, rates=None)
    plan_u = planner.plan(uniform, Objective(metric="mean"))
    assert plan.score != plan_u.score
    # a clone trigger rides the rate-aware bootstrap sweep
    clone = PolicyCandidate("clone", quantile=0.9)
    plan_c = planner.plan(
        spec,
        Objective(metric="p99", utilization=0.5, policies=(clone,)),
    )
    assert plan_c.policy in (PolicyCandidate(), clone)
    # uniform fleets still plan fine
    ok = ClusterSpec(n_workers=8, dist=Exponential(mu=2.0))
    assert EmpiricalPlanner(
        n_trials=400, seed=0, n_resamples=2
    ).plan(ok, Objective(metric="mean")).n_batches in (1, 2, 4, 8)


def test_arrivals_override_changes_sweep_but_default_is_poisson():
    """BUGFIX pin: load-aware sweeps always drew Poisson arrivals even when
    the engine ran bursty traffic.  An explicit offsets override must (a)
    change the samples, (b) leave the no-override path bit-identical, and
    (c) consume no RNG (the service draw matrix is unchanged)."""
    bursty = MMPPArrivals(rate=8.0).sample(np.random.default_rng(2), 1_200)
    base = sweep_sojourn(
        FLEET_DIST, N_FLEET, arrival_rate=8.0, n_jobs=1_200, seed=5,
    )
    again = sweep_sojourn(
        FLEET_DIST, N_FLEET, arrival_rate=8.0, n_jobs=1_200, seed=5,
    )
    over = sweep_sojourn(
        FLEET_DIST, N_FLEET, arrival_rate=8.0, n_jobs=1_200, seed=5,
        arrivals=bursty,
    )
    np.testing.assert_array_equal(base.samples, again.samples)
    assert not np.array_equal(base.samples, over.samples)
    # same fleet CRN matrix: the all-B first-job service identity still
    # holds between the two sweeps (arrivals never consume service draws)
    with pytest.raises(ValueError):
        sweep_sojourn(
            FLEET_DIST, N_FLEET, arrival_rate=8.0, n_jobs=64, seed=5,
            arrivals=np.array([1.0, 0.5]),  # decreasing
        )


def test_mmpp_override_matches_engine_measured_sojourn():
    """The sweep under the engine's ACTUAL (bursty) job-arrival offsets
    must predict the sojourn the event-driven master measures under the
    same offsets — and the Poisson default must not (it underestimates
    bursty queueing)."""
    n_groups, n_jobs = 4, 3_000
    offsets = MMPPArrivals(
        rate=6.0, burstiness=8.0, burst_fraction=0.2, mean_cycle=20.0
    ).sample(np.random.default_rng(3), n_jobs)
    dist = Exponential(mu=2.0)
    swept = simulate_sojourn(
        dist, n_groups, n_groups, arrival_rate=6.0, n_jobs=n_jobs, seed=9,
        arrivals=offsets,
    )
    poisson = simulate_sojourn(
        dist, n_groups, n_groups, arrival_rate=6.0, n_jobs=n_jobs, seed=9,
    )
    svc_rng = np.random.default_rng(17)
    master = EventDrivenMaster(
        n_groups, lambda job, g: svc_rng.exponential(1 / dist.mu, 1),
        policy=QueuePolicy(max_batch_size=1),
    )
    for i, a in enumerate(offsets):
        master.submit(Request(request_id=i, arrival=float(a)))
    jobs = master.run()
    measured = np.array([j.completed - j.requests[0].arrival for j in jobs])
    warm = n_jobs // 10
    m_measured = float(np.mean(measured[warm:]))
    m_swept = float(np.mean(swept.samples))
    m_poisson = float(np.mean(poisson.samples))
    assert m_swept == pytest.approx(m_measured, rel=0.15)
    # the Poisson stand-in misses the bursty queueing by far more than the
    # override's residual error
    assert abs(m_poisson - m_measured) > 3 * abs(m_swept - m_measured)


# -- planner portfolio decisions ----------------------------------------------

def test_plan_policy_lands_and_mirrors_clone_trigger():
    pols = (
        PolicyCandidate("clone", quantile=0.9),
        PolicyCandidate("relaunch", quantile=0.9),
        PolicyCandidate("hedged", hedge_fraction=0.2),
    )
    plan = SimulatedPlanner(n_trials=2_000, seed=3).plan(
        ClusterSpec(n_workers=N_FLEET, dist=FLEET_DIST),
        Objective(metric="p99", utilization=0.7, policies=pols),
    )
    assert plan.policy is not None
    # the winner is one of the offered candidates or the 'none' baseline,
    # and a clone winner carries its trigger on Plan.policy itself
    assert plan.policy in (PolicyCandidate(), *pols)
    if plan.policy.kind == "clone":
        assert plan.policy.quantile == 0.9


def test_portfolio_beats_or_ties_plain_baseline_by_construction():
    """The 'none' baseline always rides the sweep, so the adopted candidate
    can never score worse than plain replication at the chosen B (shared
    CRN makes the comparison exact, not statistical)."""
    pols = (PolicyCandidate("clone", quantile=0.9),)
    planner = SimulatedPlanner(n_trials=2_000, seed=3)
    spec = ClusterSpec(n_workers=N_FLEET, dist=FLEET_DIST)
    obj = Objective(metric="p99", utilization=0.7, policies=pols)
    plan = planner.plan(spec, obj)
    plain = planner.plan(
        spec, Objective(metric="p99", utilization=0.7)
    )
    assert plan.score <= plain.score + 1e-12


# -- online adoption (engine + tuner) -----------------------------------------

_ENGINE = dict(
    n_server_groups=8, n_batches=8, batch_size=2, delta=0.02, mu=2.0,
    utilization=0.7, execute_model=False, seed=0, tuner=True,
    planner_mode="simulate",
)
_PORTFOLIO = (
    PolicyCandidate("clone", quantile=0.9),
    PolicyCandidate("relaunch", quantile=0.9),
    PolicyCandidate("hedged", hedge_fraction=0.3),
)


@pytest.mark.parametrize("engine_kw, won, live, master_type", [
    ({"policy_candidates": _PORTFOLIO},
     PolicyCandidate("hedged", hedge_fraction=0.3),
     PolicyCandidate("hedged", hedge_fraction=0.3), HedgedDispatchPolicy),
    # clone mode: plain replication won, so the clone is switched off
    ({"speculation_quantile": 0.8}, PolicyCandidate(), None, None),
], ids=["portfolio_hedged", "clone_disabled"])
def test_engine_adopts_replan_policy(
    monkeypatch, engine_kw, won, live, master_type
):
    """A load-aware re-plan that swept (B, policy) cells installs the
    winning candidate on the live engine — including "don't mitigate at
    this B"."""
    eng = ReplicatedServingEngine(ServeEngineConfig(**_ENGINE, **engine_kw))
    assert eng.objective.policies is not None
    plan = eng.planner.plan(
        ClusterSpec(n_workers=8, dist=eng.dist),
        Objective(metric="mean", arrival_rate=4.0,
                  policies=eng.objective.policies),
    )
    plan = dataclasses.replace(
        plan, policy=won, replication=ReplicationPlan(n_data=8, n_batches=4),
    )
    rp = RescalePlan(old_batches=8, new_batches=4, predicted_old=1.0,
                     predicted_new=0.5, fit=None, step=0, plan=plan)
    monkeypatch.setattr(eng.tuner, "maybe_replan", lambda: rp)
    eng.serve(20)
    assert eng.plan.n_batches == 4
    assert eng.policy == live
    master = eng._speculation_policy()
    if master_type is None:
        assert master is None
    else:
        assert isinstance(master, master_type)


@pytest.mark.parametrize("engine_kw, won, master_type", [
    ({"policy_candidates": _PORTFOLIO},
     PolicyCandidate("relaunch", quantile=0.9), RelaunchPolicy),
    ({}, PolicyCandidate("clone", quantile=0.95), ClonePolicy),
], ids=["clone_to_relaunch", "clone_trigger_change"])
def test_engine_adopts_policy_switch_at_same_b(
    monkeypatch, engine_kw, won, master_type
):
    """A sweep that keeps B but flips the best candidate (another kind, or
    another trigger) still updates the engine — a policy change needs no
    drain."""
    eng = ReplicatedServingEngine(ServeEngineConfig(
        **_ENGINE, speculation_quantile=0.8, **engine_kw
    ))
    assert eng.policy == PolicyCandidate("clone", quantile=0.8)
    lp = eng.planner.plan(
        ClusterSpec(n_workers=8, dist=eng.dist, feasible_b=(8,)),
        Objective(metric="mean", arrival_rate=4.0,
                  policies=eng.objective.policies),
    )
    lp = dataclasses.replace(lp, policy=won)
    monkeypatch.setattr(eng.tuner, "maybe_replan", lambda: None)
    eng.tuner.last_plan = lp
    eng.serve(10)
    assert eng.plan.n_batches == 8  # no move
    assert eng.policy == won
    assert isinstance(eng._speculation_policy(), master_type)


def test_tuner_objective_carries_policy_portfolio():
    pols = (PolicyCandidate("relaunch", quantile=0.9),)
    tuner = StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=4),
        TunerConfig(mode="simulate"),
        policy_candidates=pols,
        arrival_offsets=np.cumsum(np.full(32, 0.5)),
    )
    tuner.observe_load(3.0)
    obj = tuner.objective()
    assert obj.policies == (PolicyCandidate(), *pols)
    assert len(obj.arrivals) == 32


# -- the live policy -> portfolio rule ----------------------------------------

_CLONE_80 = PolicyCandidate("clone", quantile=0.8)
_RELAUNCH_90 = PolicyCandidate("relaunch", quantile=0.9)


@pytest.mark.parametrize("live, candidates, portfolio", [
    (_CLONE_80, (_RELAUNCH_90,), (_RELAUNCH_90,)),  # the portfolio wins
    (_CLONE_80, None, (_CLONE_80,)),  # the live policy alone
    (None, None, None),  # nothing to score: plain replication
], ids=["portfolio", "live", "off"])
def test_straggler_portfolio_rule(live, candidates, portfolio):
    assert straggler_portfolio(live, candidates) == portfolio


def test_clone_mode_engine_plans_with_the_stability_gate():
    """An engine in clone mode scores (none, clone q) as a portfolio, so a
    clone cell whose charged utilization reaches 1 cannot win — even where
    its finite-window samples look better than plain replication."""
    clone = PolicyCandidate("clone", quantile=0.5)
    eng = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=16, n_batches=16, batch_size=4, delta=0.5, mu=2.0,
        utilization=0.8, execute_model=False, seed=0,
        planner_mode="simulate", speculation_quantile=0.5,
    ))
    obj, spec = eng.objective, eng.cluster_spec
    assert obj.policies == (PolicyCandidate(), clone)
    assert eng.tuner.policy_candidates == (clone,)
    assert obj.charged_utilization(spec, clone) >= 1.0
    plan = eng.planner.plan(spec, obj)
    assert plan.policy == PolicyCandidate()
    # ungated, the clone cell would have won at the chosen B
    res = sweep_sojourn_policies(
        spec.dist, spec.n_workers, arrival_rate=obj.offered_rate(spec),
        policies=obj.policies, n_jobs=eng.planner.n_trials,
        seed=eng.planner.seed, feasible_b=spec.feasible_batches(),
        job_load=obj.job_load,
    )
    s = res.splits.index(plan.n_batches)
    assert res.samples[0, s, 1].mean() < res.samples[0, s, 0].mean()
