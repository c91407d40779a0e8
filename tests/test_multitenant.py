"""Multi-tenant SLO serving: per-class objectives, WFQ fair sharing,
overload shedding in the planning sweep — plus the regression pins for the
two telemetry bugs this PR fixes (batch-granularity miss accounting and
redundancy-blind capacity accounting).

All CPU-fast (model execution off); the accelerator parity cells run at
reduced trial counts.
"""

import math

import numpy as np
import pytest

from _prop import given, settings, st
from repro.core import (
    ClusterSpec,
    CodingCandidate,
    EmpiricalPlanner,
    Exponential,
    Objective,
    PolicyCandidate,
    ShedPolicy,
    ShiftedExponential,
    SimulatedPlanner,
    SloClass,
    StragglerTuner,
    TunerConfig,
    simulate_sojourn_serving,
    sweep_sojourn_serving,
)
from repro.core.order_stats import Empirical
from repro.core.replication import ReplicationPlan
from repro.serving import (
    AdmissionQueue,
    EventDrivenMaster,
    MultiTenantArrivals,
    PoissonArrivals,
    QueuePolicy,
    ReplicatedServingEngine,
    Request,
    ServeEngineConfig,
)

CLASSES = (
    SloClass("premium", share=0.3, weight=4.0, deadline=0.8, miss_target=0.05),
    SloClass("batch", share=0.7, weight=1.0),
)


def _engine(**kw) -> ReplicatedServingEngine:
    base = dict(
        n_server_groups=8, n_batches=4, batch_size=4, utilization=0.7,
        arrival_kind="multitenant", queue_discipline="wfq",
        slo_classes=CLASSES, max_wait=2.0, execute_model=False,
        straggler_policy="none", seed=3,
    )
    base.update(kw)
    return ReplicatedServingEngine(ServeEngineConfig(**base))


# -- multi-tenant arrivals ----------------------------------------------------

def test_multitenant_arrivals_labels_and_shares():
    rng = np.random.default_rng(0)
    proc = MultiTenantArrivals(
        rate=5.0, classes=(("premium", 0.25), ("batch", 0.75))
    )
    t, labels = proc.sample_with_classes(rng, 8_000, start=1.0)
    assert t[0] >= 1.0 and (np.diff(t) > 0).all()
    assert set(labels) == {"premium", "batch"}
    frac = labels.count("premium") / len(labels)
    assert frac == pytest.approx(0.25, abs=0.02)


def test_multitenant_arrivals_diurnal_and_bursts_raise_variance():
    rng = np.random.default_rng(1)
    plain = MultiTenantArrivals(rate=5.0).sample(rng, 20_000)
    rng = np.random.default_rng(1)
    rough = MultiTenantArrivals(
        rate=5.0, diurnal_amplitude=0.8, diurnal_period=50.0,
        burst_rate=0.2, burst_size=30, burst_span=0.5,
    ).sample(rng, 20_000)
    window = 4.0
    cv = lambda t: (c := np.bincount((t / window).astype(int))).var() / c.mean()
    assert cv(rough) > 2.0 * cv(plain)


# -- WFQ admission queue ------------------------------------------------------

def test_wfq_long_run_shares_match_weights():
    q = AdmissionQueue(QueuePolicy(
        discipline="wfq", class_weights=(("a", 3.0), ("b", 1.0))
    ))
    for i in range(300):
        q.push(Request(request_id=2 * i, arrival=float(i), slo="a"))
        q.push(Request(request_id=2 * i + 1, arrival=float(i) + 0.5, slo="b"))
    popped = [q.pop().slo for _ in range(400)]
    share_a = popped.count("a") / len(popped)
    # stride scheduling over backlogged lanes: share within one stride of 3/4
    assert share_a == pytest.approx(0.75, abs=0.02)


@settings(max_examples=12, deadline=None)
@given(w=st.floats(min_value=1.0, max_value=64.0))
def test_wfq_never_starves_the_light_class(w):
    q = AdmissionQueue(QueuePolicy(
        discipline="wfq", class_weights=(("heavy", w), ("light", 1.0))
    ))
    n = 256
    for i in range(n):
        q.push(Request(request_id=2 * i, arrival=float(i), slo="heavy"))
        q.push(Request(request_id=2 * i + 1, arrival=float(i), slo="light"))
    k = 128
    light = sum(q.pop().slo == "light" for _ in range(k))
    # a backlogged lane of weight 1 gets at least its stride share, minus
    # one pop of slack for pass-alignment at the start
    assert light >= math.floor(k / (1.0 + w)) - 1
    assert light >= 1  # no starvation whatever the weight ratio


def test_wfq_idle_class_cannot_burst_on_accrued_credit():
    q = AdmissionQueue(QueuePolicy(
        discipline="wfq", class_weights=(("a", 1.0), ("b", 1.0))
    ))
    for i in range(64):
        q.push(Request(request_id=i, arrival=float(i), slo="a"))
    for _ in range(64):
        q.pop()
    # b was idle the whole time; on (re)activation it joins at the current
    # virtual time rather than replaying 64 pops of credit
    for i in range(8):
        q.push(Request(request_id=100 + i, arrival=100.0 + i, slo="b"))
        q.push(Request(request_id=200 + i, arrival=100.0 + i, slo="a"))
    popped = [q.pop().slo for _ in range(8)]
    assert popped.count("b") <= 5  # alternates, no catch-up burst


def test_wfq_eviction_is_weight_aware_and_equal_weights_never_evict():
    q = AdmissionQueue(QueuePolicy(
        discipline="wfq", class_weights=(("gold", 4.0), ("econ", 1.0))
    ))
    q.push(Request(request_id=0, arrival=0.0, slo="econ"))
    q.push(Request(request_id=1, arrival=1.0, slo="econ"))
    victim = q.evict_for(Request(request_id=2, arrival=2.0, slo="gold"))
    assert victim is not None and victim.request_id == 1  # newest of cheapest
    assert len(q) == 1
    # equal weight: newcomer is shed instead (None)
    assert q.evict_for(Request(request_id=3, arrival=3.0, slo="econ")) is None


# -- max_wait contract + formation throttle (live master) ---------------------

def test_max_wait_bounds_oldest_waiting_request():
    """Formation fires when the OLDEST queued request has waited max_wait:
    no request's formation wait ever exceeds the bound."""
    max_wait = 0.4
    master = EventDrivenMaster(
        n_groups=4,
        service_sampler=lambda job, g: np.full(2, 0.05),
        policy=QueuePolicy(max_batch_size=4, max_wait=max_wait),
    )
    rng = np.random.default_rng(0)
    t = PoissonArrivals(rate=3.0).sample(rng, 60)
    for i, a in enumerate(t):
        master.submit(Request(request_id=i, arrival=float(a)))
    master.run()
    assert master.completed_jobs
    for job in master.completed_jobs:
        oldest = min(r.arrival for r in job.requests)
        assert job.formed_at - oldest <= max_wait + 1e-9


def test_queue_cap_throttles_formation_and_conserves_requests():
    """With a cap, overload backlog accumulates (and sheds) in the
    admission queue instead of draining into the unbounded formed buffer;
    max_wait timers still bypass the throttle, and every request resolves
    as served or dropped."""
    policy = QueuePolicy(
        max_batch_size=2, max_wait=0.5, discipline="wfq",
        class_weights=(("gold", 4.0), ("econ", 1.0)), queue_cap=6,
    )
    master = EventDrivenMaster(
        n_groups=2,
        service_sampler=lambda job, g: np.full(1, 1.0),  # slow fleet
        policy=policy,
    )
    n = 80
    for i in range(n):
        master.submit(Request(
            request_id=i, arrival=0.01 * i,
            slo="gold" if i % 4 == 0 else "econ",
        ))
    master.run()
    served = sum(len(j.requests) for j in master.completed_jobs)
    dropped = len(master.dropped_requests)
    assert dropped > 0
    assert served + dropped == n  # conservation, nothing stuck queued
    # weight-aware shedding: overload pressure lands MOSTLY on the light
    # class (a heavy arrival can still be shed when the backlog is all
    # heavy — eviction needs a strictly-cheaper victim)
    drop_econ = sum(r.slo == "econ" for r in master.dropped_requests)
    n_econ, n_gold = 3 * n // 4, n // 4
    assert drop_econ / n_econ > (dropped - drop_econ) / n_gold
    for job in master.completed_jobs:
        oldest = min(r.arrival for r in job.requests)
        assert job.formed_at - oldest <= policy.max_wait + 1e-9


def test_swap_policy_moves_scalar_knobs_but_protects_lane_state():
    master = EventDrivenMaster(
        n_groups=2,
        service_sampler=lambda job, g: np.full(1, 0.1),
        policy=QueuePolicy(max_batch_size=4, max_wait=math.inf),
    )
    master.swap_policy(QueuePolicy(max_batch_size=4, max_wait=0.25))
    assert master.policy.max_wait == 0.25
    with pytest.raises(ValueError, match="discipline"):
        master.swap_policy(QueuePolicy(
            max_batch_size=4, discipline="wfq", class_weights=(("a", 1.0),)
        ))


# -- serving sweep: CRN parity + scoring --------------------------------------

SWEEP_KW = dict(
    n_workers=8, request_rate=9.0, batch_size=4, slo_classes=CLASSES,
    policies=(PolicyCandidate(), PolicyCandidate("hedged", hedge_fraction=1.0)),
    max_waits=(0.3, math.inf),
    sheds=(ShedPolicy(), ShedPolicy("cap", cap=24)),
    n_requests=1_500, seed=7, feasible_b=(2, 4), job_load=0.5,
)


def test_serving_sweep_cells_bit_match_standalone_simulation():
    """Every (B, policy, max_wait, shed) cell of the sweep reproduces the
    standalone single-cell simulation bit for bit at the same seed — the
    shared-CRN contract extended to the two new axes."""
    dist = ShiftedExponential(delta=0.05, mu=2.0)
    res = sweep_sojourn_serving(dist, **SWEEP_KW)
    for si, b in enumerate(res.splits):
        for pi, pol in enumerate(res.policies):
            for wi, mw in enumerate(res.max_waits):
                for hi, shed in enumerate(res.sheds):
                    sim = simulate_sojourn_serving(
                        dist, SWEEP_KW["n_workers"], b,
                        SWEEP_KW["request_rate"], SWEEP_KW["batch_size"],
                        CLASSES, pol, max_wait=mw, shed=shed,
                        n_requests=SWEEP_KW["n_requests"],
                        seed=SWEEP_KW["seed"],
                        job_load=SWEEP_KW["job_load"],
                    )
                    np.testing.assert_array_equal(
                        res.request_latency(0, si, pi, wi, hi), sim.latency
                    )


def test_serving_sweep_shed_conservation_per_class():
    dist = Exponential(mu=2.0)
    res = sweep_sojourn_serving(dist, **SWEEP_KW)
    for si in range(len(res.splits)):
        for wi in range(len(res.max_waits)):
            for hi in range(len(res.sheds)):
                rj = res.req_job[0, si, wi, hi]
                lat = res.request_latency(0, si, 0, wi, hi)
                # shed <=> NaN latency; served + shed == every request
                np.testing.assert_array_equal(np.isnan(lat), rj < 0)
                fracs = res.class_shed_fractions(0, si, wi, hi)
                assert np.all((fracs >= 0) & (fracs <= 1))
                if res.sheds[hi].kind == "none":
                    assert not np.any(rj < 0)


def test_serving_sweep_accelerator_backends_agree_with_numpy():
    """jax (and pallas-interpret) serving cells agree with numpy at
    distribution level — formation is shared, so req_job/formed are
    bit-identical and only the f32 sojourn recursion differs."""
    jax = pytest.importorskip("jax")
    del jax
    dist = Exponential(mu=2.0)
    kw = dict(SWEEP_KW, n_requests=600, feasible_b=(4,))
    ref = sweep_sojourn_serving(dist, **kw)
    for backend in ("jax", "pallas"):
        res = sweep_sojourn_serving(dist, **kw, backend=backend)
        assert res.backend == backend
        np.testing.assert_array_equal(res.req_job, ref.req_job)
        for pi in range(len(ref.policies)):
            for wi in range(len(ref.max_waits)):
                for hi in range(len(ref.sheds)):
                    a = ref.request_latency(0, 0, pi, wi, hi)
                    b = res.request_latency(0, 0, pi, wi, hi)
                    np.testing.assert_array_equal(np.isnan(a), np.isnan(b))
                    a, b = a[~np.isnan(a)], b[~np.isnan(b)]
                    assert np.nanmean(b) == pytest.approx(
                        np.nanmean(a), rel=2e-3
                    )
                    assert np.quantile(b, 0.99) == pytest.approx(
                        np.quantile(a, 0.99), rel=5e-3
                    )


# -- objective / planner ------------------------------------------------------

def test_objective_slo_validation():
    with pytest.raises(ValueError, match="load"):
        Objective(slo_classes=CLASSES, batch_size=4)
    with pytest.raises(ValueError, match="batch_size"):
        Objective(slo_classes=CLASSES, utilization=0.7)
    with pytest.raises(ValueError, match="duplicate"):
        Objective(
            slo_classes=(SloClass("a"), SloClass("a")),
            batch_size=4, utilization=0.7,
        )
    obj = Objective(slo_classes=CLASSES, batch_size=4, utilization=0.7)
    spec = ClusterSpec(n_workers=8, dist=Exponential(mu=2.0))
    assert obj.request_rate(spec) == pytest.approx(
        obj.offered_rate(spec) * 4
    )
    # a shed portfolio always races the no-shed baseline
    obj2 = Objective(
        slo_classes=CLASSES, batch_size=4, utilization=0.7,
        sheds=(ShedPolicy("cap", cap=16),),
    )
    assert obj2.sheds[0].kind == "none"


def test_offered_rate_charges_redundant_work_regression():
    """REGRESSION (capacity accounting): utilization-anchored offered rate
    must divide by the policy's expected work factor — pre-fix, a
    full-hedging cell was scored at the same offered rate as a plain cell
    even though it performs ~1.5x the work per job."""
    spec = ClusterSpec(n_workers=8, dist=ShiftedExponential(delta=0.5, mu=2.0))
    obj = Objective(utilization=0.9, job_load=1.0)
    hedged = PolicyCandidate("hedged", hedge_fraction=1.0)
    base = obj.offered_rate(spec)
    charged = obj.offered_rate(spec, policy=hedged)
    wf = hedged.work_factor(spec.dist.scaled(obj.job_load))
    assert wf > 1.4  # delta = 1/mu => factor 1.5
    assert charged == pytest.approx(base / wf)
    # plain replication and an explicit arrival_rate are both unchanged
    assert obj.offered_rate(spec, policy=PolicyCandidate()) == base
    rate_obj = Objective(arrival_rate=3.0)
    assert rate_obj.offered_rate(spec, policy=hedged) == 3.0
    # charged utilization prices the redundancy back in
    assert obj.charged_utilization(spec, hedged) == pytest.approx(0.9 * wf)


def test_capacity_accounting_flips_the_planner_winner_regression():
    """REGRESSION (winner flip): near saturation, full hedging's redundant
    work makes it INFEASIBLE once charged honestly — pre-fix the planner
    picked hedged (it looked like free variance reduction at unchanged
    utilization); post-fix the stability gate hands the win to plain
    replication."""
    spec = ClusterSpec(n_workers=8, dist=ShiftedExponential(delta=0.5, mu=2.0))
    hedged = PolicyCandidate("hedged", hedge_fraction=1.0)
    obj = Objective(
        utilization=0.95, job_load=1.0,
        policies=(PolicyCandidate(), hedged),
    )
    assert obj.charged_utilization(spec, hedged) > 1.0  # unstable if charged
    assert obj.charged_utilization(spec, PolicyCandidate()) < 1.0
    plan = SimulatedPlanner(n_trials=2_000, seed=0).plan(spec, obj)
    assert plan.policy is None or not plan.policy.enabled


def test_simulated_planner_serving_plan_lands_full_cell():
    spec = ClusterSpec(n_workers=8, dist=ShiftedExponential(delta=0.02, mu=2.0))
    obj = Objective(
        utilization=0.85, batch_size=4, slo_classes=CLASSES, job_load=0.5,
        max_waits=(0.3, 2.0), sheds=(ShedPolicy("cap", cap=24),),
        policies=(PolicyCandidate(),),
    )
    plan = SimulatedPlanner(n_trials=1_500, seed=1).plan(spec, obj)
    assert plan.n_batches in (1, 2, 4, 8)
    assert plan.max_wait in (0.3, 2.0)
    assert plan.shed is not None and plan.shed.kind in ("none", "cap")
    report = dict(plan.class_report)
    assert set(report) == {"premium", "batch"}
    assert math.isnan(report["batch"])  # no deadline => no miss concept
    assert 0.0 <= report["premium"] <= 1.0
    # deterministic at fixed seed
    again = SimulatedPlanner(n_trials=1_500, seed=1).plan(spec, obj)
    assert (again.n_batches, again.max_wait, again.shed) == (
        plan.n_batches, plan.max_wait, plan.shed
    )


def test_empirical_planner_rejects_slo_objectives():
    x = np.random.default_rng(0).exponential(0.5, 256)
    spec = ClusterSpec(n_workers=8, dist=Empirical(x))
    obj = Objective(slo_classes=CLASSES, batch_size=4, utilization=0.7)
    with pytest.raises(ValueError, match="SimulatedPlanner"):
        EmpiricalPlanner(n_trials=500, seed=0).plan(spec, obj)


# -- tuner: per-class miss telemetry ------------------------------------------

def _tuner(**kw) -> StragglerTuner:
    return StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=4),
        TunerConfig(window_steps=16),
        **kw,
    )


def test_tuner_class_miss_windows_and_guards():
    t = _tuner(slo_classes=CLASSES, serving_batch_size=4)
    t.observe_deadline_misses(1, 1, slo="premium")
    t.observe_deadline_misses(0, 1, slo="premium")
    t.observe_deadline_misses(0, 1, slo="batch")
    assert t.class_miss_rates() == {"premium": 0.5, "batch": 0.0}
    assert t.observed_miss_rate == pytest.approx(1 / 3)
    assert t._class_target_breached()  # premium target is 5%
    t.apply(type("RP", (), {"new_batches": 4})())
    assert t.class_miss_rates() == {}
    with pytest.raises(ValueError, match="serving_batch_size"):
        _tuner(slo_classes=CLASSES)
    with pytest.raises(ValueError, match="only apply"):
        _tuner(max_wait_candidates=(0.5,))
    with pytest.raises(ValueError, match="mutually"):
        _tuner(
            slo_classes=CLASSES, serving_batch_size=4,
            coding_candidates=(CodingCandidate(scheme="mds", s=1),),
        )


def test_tuner_objective_carries_serving_axes():
    t = _tuner(
        slo_classes=CLASSES, serving_batch_size=4,
        max_wait_candidates=(0.5, 2.0),
        shed_candidates=(ShedPolicy("cap", cap=16),),
        policy_candidates=(PolicyCandidate(),),
    )
    t.observe_load(3.0)
    obj = t.objective(SimulatedPlanner(n_trials=100, seed=0))
    assert obj.slo_classes == CLASSES and obj.batch_size == 4
    assert obj.max_waits == (0.5, 2.0)
    assert obj.sheds[0].kind == "none" and obj.sheds[1].kind == "cap"
    # a class-incapable planner gets a plain load-aware objective
    from repro.core import AnalyticPlanner

    assert t.objective(AnalyticPlanner()).slo_classes is None


# -- engine: miss-telemetry bugfix + end-to-end -------------------------------

def test_served_miss_telemetry_is_per_request_regression():
    """REGRESSION (miss granularity): the served path used to observe one
    (n_missed, n_batch) pair per JOB while the drop path observed per
    request — partial batches then skewed the windowed rate.  Post-fix
    every observation is a single request, class-attributed."""
    eng = _engine(utilization=0.8)
    out = eng.run_load(n_requests=160)
    misses = list(eng.tuner._misses)
    assert misses
    assert all(total == 1 for _, total in misses)
    resolved = [s for s in out["stats"] if math.isfinite(s.deadline)]
    assert sum(total for _, total in misses) == len(resolved)
    # class attribution: only the deadline-carrying class reports
    assert set(eng.tuner.class_miss_rates()) == {"premium"}


def test_drop_telemetry_counts_only_deadline_carrying_requests_regression():
    """REGRESSION (drop accounting): the drop path used to count EVERY shed
    request as a deadline miss — a cap-shed best-effort request is lost
    work, not an SLO miss, and it carried no class attribution."""
    eng = _engine(utilization=0.95, seed=11)
    eng.shed = ShedPolicy("cap", cap=6)  # live admission control
    out = eng.run_load(n_requests=400)
    dropped = [s for s in out["stats"] if s.dropped]
    assert dropped, "cap shedding never engaged"
    assert any(not math.isfinite(s.deadline) for s in dropped)
    resolved = [s for s in out["stats"] if math.isfinite(s.deadline)]
    assert sum(t for _, t in eng.tuner._misses) == len(resolved)


def test_run_load_class_report_is_consistent():
    eng = _engine(utilization=0.9, seed=5)
    eng.shed = ShedPolicy("cap", cap=8)
    out = eng.run_load(n_requests=500)
    cs = out["class_stats"]
    assert set(cs) == {"premium", "batch"}
    assert sum(c["requests"] for c in cs.values()) == out["requests"]
    assert sum(c["dropped"] for c in cs.values()) == out["n_dropped"]
    for c in cs.values():
        assert c["served"] + c["dropped"] == c["requests"]
    # the global miss rate is the count-weighted fold of per-class rates
    # (only premium carries deadlines here)
    stats = out["stats"]
    premium_dl = [
        s for s in stats if s.slo == "premium" and math.isfinite(s.deadline)
    ]
    assert cs["premium"]["miss_rate"] == pytest.approx(
        sum(s.missed_deadline for s in premium_dl) / len(premium_dl)
    )
    assert out["deadline_miss_rate"] == pytest.approx(
        cs["premium"]["miss_rate"]
    )
    assert cs["batch"]["miss_rate"] is None


def test_engine_config_validation():
    with pytest.raises(ValueError, match="offered load"):
        _engine(utilization=None)
    with pytest.raises(ValueError, match="wfq"):
        ReplicatedServingEngine(ServeEngineConfig(
            queue_discipline="wfq", utilization=0.5, execute_model=False,
        ))
    with pytest.raises(ValueError, match="simulate"):
        _engine(tuner=True, planner_mode="analytic")


def test_serving_replan_adopts_max_wait_and_shed_live():
    eng = _engine(
        utilization=0.9, seed=5, tuner=True, plan_initial=True,
        planner_mode="simulate", max_wait_candidates=(0.4, 2.0),
        shed_candidates=(ShedPolicy("cap", cap=24),),
    )
    # the initial serving plan already landed a swept cell on the engine
    assert eng.max_wait in (0.4, 2.0)
    out = eng.run_load(n_requests=400)
    assert out["max_wait"] in (0.4, 2.0)
    assert out["shed"] in ("none", "cap")
    # tuner-side telemetry went through the per-class windows
    assert set(eng.tuner.class_miss_rates()) <= {"premium"}


def test_bench_multitenant_smoke():
    """Tier-1 twin of the nightly bench: headline assertions at small size.

    ``run()`` asserts the overload-protection headline internally (FIFO
    breaches the premium target, the swept plan holds every class target),
    so importing and running it IS the check; the smoke only pins the row
    contract on top.
    """
    import os
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, root)
    try:
        from benchmarks import bench_multitenant
    finally:
        sys.path.remove(root)
    rows = bench_multitenant.run(jobs=1_500)
    assert [name for name, _, _ in rows] == [
        "multitenant_overload_protection",
        "multitenant_diurnal_burst",
        "multitenant_sweep_cell",
    ]
    for _, us, derived in rows:
        assert us > 0 and isinstance(derived, str)
