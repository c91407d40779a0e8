"""Discrete-event serving subsystem: arrivals, queueing master, sojourn
simulator, load-aware planner objectives, and the engine shim — all CPU-fast
(model execution off)."""

import dataclasses
import math

import numpy as np
import pytest

from _prop import given, settings, st
from repro.core import (
    AnalyticPlanner,
    ClusterSpec,
    Exponential,
    Objective,
    PolicyCandidate,
    ReplicationPlan,
    ShiftedExponential,
    SimulatedPlanner,
    StragglerTuner,
    TunerConfig,
    simulate_sojourn,
    simulate_sojourn_policies,
    sweep_sojourn,
)
from repro.serving import (
    ClonePolicy,
    DeterministicArrivals,
    EventDrivenMaster,
    MMPPArrivals,
    PoissonArrivals,
    QueuePolicy,
    ReplicatedServingEngine,
    Request,
    ServeEngineConfig,
    TraceArrivals,
    make_arrivals,
    partition_requests,
)

# the Fig. 2-style SExp fleet used by the acceptance demonstration
N_FLEET = 16
FLEET_DIST = ShiftedExponential(delta=0.02, mu=2.0)
CLONE_90 = PolicyCandidate("clone", quantile=0.9)


# -- arrival processes --------------------------------------------------------

def test_poisson_arrivals_rate_and_order():
    rng = np.random.default_rng(0)
    t = PoissonArrivals(rate=5.0).sample(rng, 20_000, start=3.0)
    assert t[0] >= 3.0
    assert (np.diff(t) > 0).all()
    assert 20_000 / (t[-1] - 3.0) == pytest.approx(5.0, rel=0.05)


def test_deterministic_arrivals_spacing():
    rng = np.random.default_rng(0)
    t = DeterministicArrivals(rate=4.0).sample(rng, 8, start=1.0)
    np.testing.assert_allclose(np.diff(t), 0.25)
    assert t[0] == pytest.approx(1.25)


def test_mmpp_mean_rate_pinned_but_burstier_than_poisson():
    rng = np.random.default_rng(1)
    mmpp = MMPPArrivals(rate=5.0, burstiness=8.0, burst_fraction=0.2,
                        mean_cycle=20.0)
    t = mmpp.sample(rng, 40_000)
    assert 40_000 / t[-1] == pytest.approx(5.0, rel=0.1)
    # burstiness: count variance over windows far exceeds Poisson (= mean)
    window = 4.0
    counts = np.bincount((t / window).astype(int))
    assert counts.var() > 2.0 * counts.mean()


def test_trace_arrivals_replay_and_cycle():
    rng = np.random.default_rng(0)
    tr = TraceArrivals(offsets=(0.0, 1.0, 3.0))
    t = tr.sample(rng, 7, start=10.0)
    assert t[0] == pytest.approx(10.0)
    np.testing.assert_allclose(t[:3] - 10.0, [0.0, 1.0, 3.0])
    assert (np.diff(t) > 0).all()  # laps stay strictly ordered
    assert tr.mean_rate() == pytest.approx(2 / 3.0)


def test_make_arrivals_factory_and_validation():
    assert isinstance(make_arrivals("poisson", 2.0), PoissonArrivals)
    assert isinstance(make_arrivals("mmpp", 2.0), MMPPArrivals)
    with pytest.raises(ValueError):
        make_arrivals("warp", 2.0)
    with pytest.raises(ValueError):
        PoissonArrivals(rate=-1.0)
    with pytest.raises(ValueError):
        MMPPArrivals(rate=1.0, burstiness=0.5)


# -- batch partition (the legacy serve_round drop bug) ------------------------

def test_partition_requests_last_batch_absorbs_remainder():
    # the legacy engine served only b * (n // b) requests: n=10, B=4 dropped
    # requests 8 and 9.  The last slice must absorb them.
    slices = partition_requests(10, 4)
    assert slices == [(0, 2), (2, 4), (4, 6), (6, 10)]
    covered = [i for lo, hi in slices for i in range(lo, hi)]
    assert covered == list(range(10))


def test_partition_requests_divisible_matches_legacy_layout():
    assert partition_requests(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]


def test_partition_requests_fewer_than_batches():
    slices = partition_requests(3, 4)
    assert slices == [(0, 1), (1, 2), (2, 3), (3, 3)]  # trailing empty slice


# -- event-driven master ------------------------------------------------------

def _requests(arrivals, priority=None):
    return [
        Request(request_id=i, arrival=float(a),
                priority=0.0 if priority is None else priority[i])
        for i, a in enumerate(arrivals)
    ]


def test_synchronized_round_is_maxmin_rule():
    """Pre-formed batches on idle sets: completion = min over replicas, the
    paper's rule with zero queueing."""
    times = np.array([[3.0, 1.0], [2.0, 5.0]])
    master = EventDrivenMaster(2, service_sampler=None, clock=10.0)
    jobs = [
        master.submit_formed(_requests([10.0, 10.0]), at=10.0,
                             service_times=times[i])
        for i in range(2)
    ]
    master.run()
    assert jobs[0].completed == 11.0 and jobs[0].winner == 1
    assert jobs[1].completed == 12.0 and jobs[1].winner == 0
    assert master.clock == 12.0
    for job in jobs:
        for req in job.requests:
            assert req.dispatched == 10.0
            assert req.completion == job.completed


def test_fifo_queueing_second_job_waits():
    """One replica-set, two batches: the second sojourn includes the first's
    service (queue wait), the event clock advances monotonically."""
    svc = iter([np.array([2.0]), np.array([3.0])])
    master = EventDrivenMaster(
        1, service_sampler=lambda job, g: next(svc),
        policy=QueuePolicy(max_batch_size=1),
    )
    for r in _requests([0.0, 0.5]):
        master.submit(r)
    jobs = master.run()
    assert jobs[0].completed == 2.0
    assert jobs[1].dispatched == 2.0  # waited for the set to free
    assert jobs[1].completed == 5.0
    assert jobs[1].requests[0].sojourn == pytest.approx(4.5)
    assert jobs[1].requests[0].queue_wait == pytest.approx(1.5)


def test_batch_forms_at_max_size_or_max_wait():
    calls = []

    def sampler(job, g):
        calls.append(job.size)
        return np.array([0.1])

    master = EventDrivenMaster(
        4, sampler, policy=QueuePolicy(max_batch_size=3, max_wait=1.0)
    )
    # three quick arrivals -> size-3 batch at once; one straggling request
    # -> flushed by its max_wait deadline as a size-1 batch
    for r in _requests([0.0, 0.1, 0.2, 5.0]):
        master.submit(r)
    jobs = master.run()
    assert calls == [3, 1]
    assert jobs[0].formed_at == pytest.approx(0.2)
    assert jobs[1].formed_at == pytest.approx(6.0)  # 5.0 + max_wait


def test_leftover_queue_flushed_at_stream_end():
    master = EventDrivenMaster(
        2, lambda job, g: np.array([0.5]),
        policy=QueuePolicy(max_batch_size=4),  # max_wait = inf
    )
    for r in _requests([0.0, 0.1]):  # never reaches max_batch_size
        master.submit(r)
    jobs = master.run()
    assert len(jobs) == 1 and jobs[0].size == 2  # nothing dropped


def test_priority_discipline_overtakes_fifo():
    master = EventDrivenMaster(
        1, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=1, discipline="priority"),
    )
    # all queued behind a busy set; the high-priority late request forms the
    # next batch ahead of earlier low-priority ones
    for r in _requests([0.0, 0.1, 0.2], priority=[0.0, 0.0, 5.0]):
        master.submit(r)
    jobs = master.run()
    served_order = [job.requests[0].request_id for job in jobs]
    assert served_order == [0, 2, 1]


def test_first_replica_wins_telemetry():
    times = np.array([4.0, 0.5, 2.0])
    master = EventDrivenMaster(1, None)
    job = master.submit_formed(_requests([0.0]), at=0.0, service_times=times)
    master.run()
    assert job.winner == 1
    np.testing.assert_array_equal(job.used_mask(), [False, True, False])
    assert job.service == pytest.approx(0.5)


def test_reconfigure_drains_then_swaps():
    reconfigured = []

    def on_complete(job):
        if job.batch_id == 0:
            return {"n_groups": 3}
        reconfigured.append(master.n_groups)
        return None

    master = EventDrivenMaster(
        1, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=1), on_job_complete=on_complete,
    )
    for r in _requests([0.0, 0.1, 0.2]):
        master.submit(r)
    jobs = master.run()
    assert len(jobs) == 3
    assert master.reconfigurations == 1
    assert reconfigured == [3, 3]  # later jobs saw the swapped fabric
    # jobs 2 and 3 dispatched together on the widened fabric after drain
    assert jobs[1].dispatched == jobs[2].dispatched == jobs[0].completed


# -- sojourn simulator --------------------------------------------------------

def test_mm1_mean_sojourn_closed_form():
    """N=1, B=1, Exp service: M/M/1 with E[sojourn] = 1/(mu - lambda)."""
    sim = simulate_sojourn(
        Exponential(mu=2.0), 1, 1, arrival_rate=1.0, n_jobs=60_000, seed=0
    )
    assert sim.mean == pytest.approx(1.0, rel=0.08)


def test_zero_load_sojourn_is_pure_service():
    """Vanishing arrival rate: no queueing, sojourn = min of r replicas'
    service = SExp(load*delta, r*mu/load)."""
    n, b = 8, 2  # r = 4
    dist = ShiftedExponential(delta=0.3, mu=1.5)
    sim = simulate_sojourn(
        dist, n, b, arrival_rate=1e-4, n_jobs=8_000, seed=1
    )
    expected = 0.3 + 1.0 / (4 * 1.5)
    assert sim.mean == pytest.approx(expected, rel=0.05)


def test_sojourn_increases_with_load():
    means = [
        simulate_sojourn(
            FLEET_DIST, N_FLEET, 4, arrival_rate=lam, n_jobs=4_000, seed=2
        ).mean
        for lam in (2.0, 10.0, 20.0)
    ]
    assert means[0] < means[1] < means[2]


def test_sweep_sojourn_cells_bit_identical_to_single_sim():
    lam = 8.0
    sweep = sweep_sojourn(
        FLEET_DIST, N_FLEET, arrival_rate=lam, n_jobs=2_000, seed=5
    )
    for i, b in enumerate(sweep.splits):
        single = simulate_sojourn(
            FLEET_DIST, N_FLEET, b, arrival_rate=lam, n_jobs=2_000, seed=5
        )
        np.testing.assert_array_equal(sweep.samples[0, i], single.samples)


def test_sojourn_validation():
    with pytest.raises(ValueError):
        simulate_sojourn(FLEET_DIST, 16, 3, arrival_rate=1.0)  # B !| N
    with pytest.raises(ValueError):
        simulate_sojourn(FLEET_DIST, 16, 4, arrival_rate=-1.0)
    with pytest.raises(ValueError):
        simulate_sojourn(FLEET_DIST, 16, 4, arrival_rate=1.0, n_jobs=100,
                         warmup=100)


# -- load-aware planner objectives --------------------------------------------

def test_objective_load_validation():
    with pytest.raises(ValueError):
        Objective(arrival_rate=1.0, utilization=0.5)  # mutually exclusive
    with pytest.raises(ValueError):
        Objective(utilization=1.5)
    with pytest.raises(ValueError):
        Objective(arrival_rate=0.0)
    with pytest.raises(ValueError):
        Objective(job_load=0.0)
    assert not Objective(metric="p99").load_aware
    assert Objective(utilization=0.5).load_aware


def test_objective_offered_rate_conversion():
    spec = ClusterSpec(n_workers=N_FLEET, dist=FLEET_DIST)
    obj = Objective(utilization=0.7)
    # capacity anchor: N / E[service of one unit-load job on one group]
    assert obj.offered_rate(spec) == pytest.approx(
        0.7 * N_FLEET / (0.02 + 0.5)
    )
    assert Objective(arrival_rate=3.0).offered_rate(spec) == 3.0


def test_analytic_planner_rejects_load_aware():
    spec = ClusterSpec(n_workers=N_FLEET, dist=FLEET_DIST)
    with pytest.raises(ValueError, match="load-aware"):
        AnalyticPlanner().plan(spec, Objective(metric="p99", utilization=0.7))


def test_load_free_objective_unchanged_by_new_fields():
    """Batch-completion planning is byte-identical to the pre-queueing path."""
    spec = ClusterSpec(n_workers=N_FLEET, dist=FLEET_DIST)
    a = SimulatedPlanner(n_trials=2_000, seed=0).plan(spec, Objective(metric="p99"))
    b = SimulatedPlanner(n_trials=2_000, seed=0).plan(spec, Objective(metric="p99"))
    assert a.n_batches == b.n_batches
    assert a.predicted == b.predicted


# -- the acceptance demonstration --------------------------------------------
# At utilization ~0.7 (Poisson arrivals) on the Fig. 2-style SExp fleet, the
# load-aware p99 objective must pick a B whose MEASURED sojourn p99 in the
# event-driven engine beats both the batch-completion-optimal B and the
# no-replication baseline (B = N, r = 1).

def _engine_p99(n_batches: int, n_requests: int = 3_000) -> float:
    eng = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=N_FLEET, n_batches=n_batches, batch_size=4,
        prompt_len=16, gen_tokens=8, delta=0.02, mu=2.0,
        utilization=0.7, execute_model=False, seed=42,
    ))
    return eng.run_load(n_requests=n_requests)["p99_sojourn"]


def test_load_aware_plan_beats_batch_optimal_and_no_replication():
    spec = ClusterSpec(n_workers=N_FLEET, dist=FLEET_DIST)
    planner = SimulatedPlanner(n_trials=6_000, seed=0)
    batch_b = planner.plan(spec, Objective(metric="p99")).n_batches
    load_b = planner.plan(
        spec, Objective(metric="p99", utilization=0.7)
    ).n_batches
    # pinned picks: near-exponential SExp favors full diversity per batch
    # completion (Thm 2), but under load B=1 is past saturation
    assert batch_b == 1
    assert load_b == 4
    assert load_b not in (batch_b, N_FLEET)

    p99 = {b: _engine_p99(b) for b in (batch_b, load_b, N_FLEET)}
    assert p99[load_b] < p99[batch_b]
    assert p99[load_b] < p99[N_FLEET]


# -- engine: shim parity + event mode ----------------------------------------

def _shim_config(**kw):
    base = dict(n_server_groups=8, n_batches=4, batch_size=2, prompt_len=8,
                gen_tokens=4, execute_model=False, seed=3)
    base.update(kw)
    return ServeEngineConfig(**base)


def test_serve_round_shim_reproduces_legacy_latencies_bit_for_bit():
    """rates=ones, zero queueing, one synchronized round: the event-loop
    shim must equal the legacy lock-step engine draw-for-draw."""
    eng = ReplicatedServingEngine(_shim_config())
    stats = eng.serve_round()
    # the legacy engine's exact computation, replayed on a fresh rng
    sc = eng.sc
    rng = np.random.default_rng(sc.seed + 1)
    b, r = 4, 2
    n = b * sc.batch_size
    per_batch = n // b
    work = per_batch * (sc.prompt_len + sc.gen_tokens) / 100.0
    times = ShiftedExponential(sc.delta, sc.mu).scaled(work).sample(rng, (b, r))
    batch_done = times.min(axis=1)
    legacy = [float(batch_done[i // per_batch]) for i in range(n)]
    got = [s.latency for s in sorted(stats, key=lambda s: s.request_id)]
    assert got == legacy  # bit-for-bit, not approx
    assert eng.clock == float(batch_done.max())


def test_serve_round_remainder_not_dropped():
    """Regression: n_requests=10, B=4 must serve ALL 10 requests (the legacy
    engine silently served only 8)."""
    eng = ReplicatedServingEngine(_shim_config())
    stats = eng.serve_round(n_requests=10)
    assert len(stats) == 10
    assert sorted(s.request_id for s in stats) == list(range(10))
    # the remainder rides with the LAST batch: same completion time
    last = [s for s in stats if s.request_id >= 6]
    assert len({s.completion for s in last}) == 1
    assert all(np.isfinite(s.latency) and s.latency > 0 for s in stats)


def test_serve_round_ids_continue_across_rounds():
    eng = ReplicatedServingEngine(_shim_config())
    eng.serve_round(n_requests=10)
    stats = eng.serve_round(n_requests=10)
    assert sorted(s.request_id for s in stats) == list(range(10, 20))


def test_event_mode_serves_all_requests_with_queueing():
    eng = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=N_FLEET, n_batches=4, batch_size=4, delta=0.02,
        mu=2.0, utilization=0.7, execute_model=False, seed=0,
    ))
    out = eng.run_load(n_requests=1_000)
    assert out["requests"] == 1_000
    assert out["mean_queue_wait"] > 0  # real queueing happened
    assert out["p50_sojourn"] <= out["p99_sojourn"] <= out["p999_sojourn"]
    stats = out["stats"]
    assert all(np.isfinite(s.completion) for s in stats)
    assert all(s.completion >= s.dispatched >= s.arrival for s in stats)


def test_event_mode_respects_custom_arrivals_and_discipline():
    eng = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=8, n_batches=2, batch_size=2, delta=0.02, mu=2.0,
        queue_discipline="priority", max_wait=0.5, execute_model=False,
        seed=0,
    ))
    stats = eng.serve(200, arrivals=DeterministicArrivals(rate=5.0))
    assert len(stats) == 200


def test_event_mode_tuner_replans_from_sojourn_telemetry():
    """Under heavy load, a B=N start must move off no-replication, the
    re-plan objective must carry the OBSERVED arrival rate, and the final B
    must serve the tail better than staying put."""
    sc = ServeEngineConfig(
        n_server_groups=N_FLEET, n_batches=N_FLEET, batch_size=4,
        prompt_len=16, gen_tokens=8, delta=0.02, mu=2.0, utilization=0.7,
        execute_model=False, seed=2, tuner=True, metric="p99",
        planner_mode="simulate",
    )
    eng = ReplicatedServingEngine(sc)
    out = eng.run_load(n_requests=4_000)
    assert out["final_B"] < N_FLEET
    plan = eng.tuner.last_plan
    assert plan is not None and plan.objective.load_aware
    true_batch_rate = eng.objective.offered_rate(eng.cluster_spec)
    assert plan.objective.arrival_rate == pytest.approx(
        true_batch_rate, rel=0.25
    )
    # the adapted tail beats the static no-replication baseline
    static = ReplicatedServingEngine(
        dataclasses.replace(sc, tuner=False)
    ).run_load(n_requests=4_000)
    tail = sorted(out["stats"], key=lambda s: s.request_id)[2_000:]
    tail_p99 = float(np.quantile([s.latency for s in tail], 0.99))
    assert tail_p99 < static["p99_sojourn"]


def test_plan_initial_load_aware_picks_interior_b():
    eng = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=N_FLEET, batch_size=4, delta=0.02, mu=2.0,
        utilization=0.7, metric="p99", planner_mode="simulate",
        plan_initial=True, execute_model=False, seed=0,
    ))
    assert 1 < eng.plan.n_batches < N_FLEET


def test_event_mode_needs_a_load_spec():
    eng = ReplicatedServingEngine(_shim_config())
    with pytest.raises(ValueError, match="arrival_rate"):
        eng.serve(10)


def test_config_rejects_ambiguous_load_spec():
    with pytest.raises(ValueError, match="not both"):
        ReplicatedServingEngine(
            _shim_config(arrival_rate=10.0, utilization=0.7)
        )


def test_serve_round_remainder_priced_for_its_true_size():
    """The remainder-absorbing last batch is charged its REAL work: its
    latency scales up from the same draws by (actual size / per_batch)."""
    eng = ReplicatedServingEngine(_shim_config())
    stats = eng.serve_round(n_requests=10)  # B=4, per_batch=2, last size 4
    sc = eng.sc
    rng = np.random.default_rng(sc.seed + 1)
    work = 2 * (sc.prompt_len + sc.gen_tokens) / 100.0
    times = ShiftedExponential(sc.delta, sc.mu).scaled(work).sample(rng, (4, 2))
    times[3] *= 2.0  # 4 requests on a batch priced for 2
    by_id = {s.request_id: s for s in stats}
    assert by_id[0].latency == float(times[0].min())
    assert by_id[9].latency == float(times[3].min())


def test_drained_jobs_still_report_completion():
    """Jobs finishing while a re-plan drain is pending must still fire
    on_job_complete (model work + telemetry would otherwise vanish)."""
    seen = []

    def on_complete(job):
        seen.append(job.batch_id)
        return {"n_groups": 1} if job.batch_id == 0 else None

    master = EventDrivenMaster(
        2, lambda job, g: np.array([1.0 if job.batch_id == 0 else 5.0]),
        policy=QueuePolicy(max_batch_size=1), on_job_complete=on_complete,
    )
    # both dispatch immediately; job 0 completes first and requests a
    # reconfig, job 1 departs DURING the drain
    for r in _requests([0.0, 0.0]):
        master.submit(r)
    jobs = master.run()
    assert len(jobs) == 2
    assert seen == [0, 1]
    assert master.reconfigurations == 1


# -- speculative re-dispatch --------------------------------------------------

def test_speculation_clone_wins_and_cancels_originals():
    """A late batch is cloned onto an idle set; the faster clone completes
    the job, the originals are cancelled (used_mask all False), and both
    sets free at the winner's time."""
    svc = iter([np.array([10.0]), np.array([1.0])])
    master = EventDrivenMaster(
        2, lambda job, g: next(svc),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=ClonePolicy(max_clones=1, threshold=lambda job: 2.0),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    job = jobs[0]
    assert master.speculations == 1
    assert job.n_clones == 1 and job.winner_clone == 0
    assert job.clone_dispatched == [2.0]  # trigger at dispatch + threshold
    assert job.completed == pytest.approx(3.0)  # 2.0 + clone's 1.0
    assert not job.used_mask().any()  # no original replica's result used
    assert sorted(job.groups) == [0, 1]
    assert sorted(master._idle) == [0, 1]  # both sets freed at completion


def test_speculation_after_original_completes_is_noop():
    master = EventDrivenMaster(
        2, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=ClonePolicy(threshold=lambda job: 2.0),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    assert master.speculations == 0
    assert jobs[0].n_clones == 0 and jobs[0].winner_clone == -1
    assert jobs[0].completed == pytest.approx(1.0)


def test_speculation_losing_clone_is_cancelled():
    """A clone slower than the original changes nothing about completion;
    it is cancelled at the original's response and the set frees then."""
    svc = iter([np.array([3.0]), np.array([10.0])])
    master = EventDrivenMaster(
        2, lambda job, g: next(svc),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=ClonePolicy(max_clones=1, threshold=lambda job: 1.0),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    job = jobs[0]
    assert master.speculations == 1
    assert job.winner_clone == -1  # original replica won
    np.testing.assert_array_equal(job.used_mask(), [True])
    assert job.completed == pytest.approx(3.0)
    assert sorted(master._idle) == [0, 1]


def test_speculation_clone_budget_exhausted():
    """The trigger re-arms after each clone but stops at max_clones, even
    while the job stays late and idle sets remain."""
    master = EventDrivenMaster(
        4, lambda job, g: np.array([100.0]),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=ClonePolicy(max_clones=2, threshold=lambda job: 1.0),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    assert jobs[0].n_clones == 2  # budget, not the number of idle sets
    assert master.speculations == 2
    zero = EventDrivenMaster(
        2, lambda job, g: np.array([5.0]),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=ClonePolicy(max_clones=0, threshold=lambda job: 1.0),
    )
    zero.submit(Request(request_id=0, arrival=0.0))
    zero.run()
    assert zero.speculations == 0


def test_speculation_needs_an_idle_set():
    """B=1 leaves no set to clone onto: speculation never fires (and the
    re-armed trigger terminates cleanly)."""
    master = EventDrivenMaster(
        1, lambda job, g: np.array([5.0]),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=ClonePolicy(max_clones=3, threshold=lambda job: 1.0),
    )
    master.submit(Request(request_id=0, arrival=0.0))
    jobs = master.run()
    assert master.speculations == 0
    assert jobs[0].completed == pytest.approx(5.0)


def test_speculation_empirical_threshold_calibrates():
    """Without a caller-supplied threshold the master self-calibrates from
    its window of observed batch services once min_observations accrue."""
    services = iter([1.0, 1.0, 1.0, 1.0, 10.0, 1.0])
    master = EventDrivenMaster(
        2, lambda job, g: np.array([next(services)]),
        policy=QueuePolicy(max_batch_size=1),
        straggler_policy=ClonePolicy(
            late_quantile=0.5, max_clones=1, min_observations=4
        ),
    )
    for i, a in enumerate([0.0, 2.0, 4.0, 6.0, 8.0]):
        master.submit(Request(request_id=i, arrival=a))
    jobs = master.run()
    # jobs 0-3 complete before the window fills; job 4 (service 10) trips
    # the ~1.0 empirical threshold at t=9 and its clone finishes at 10
    assert master.speculations == 1
    assert jobs[-1].completed == pytest.approx(10.0)


def test_mm1_with_speculation_matches_plain_and_closed_form():
    """B=1 pins the speculative simulator: no spare set means no clone can
    ever launch, so the event-driven speculative path must reproduce the
    plain recursion draw-for-draw AND the M/M/1 closed form."""
    plain = simulate_sojourn(
        Exponential(mu=2.0), 1, 1, arrival_rate=1.0, n_jobs=20_000, seed=0
    )
    (spec,) = simulate_sojourn_policies(
        Exponential(mu=2.0), 1, 1, arrival_rate=1.0,
        policies=(CLONE_90,), n_jobs=20_000, seed=0,
    )
    np.testing.assert_array_equal(spec, plain.samples)
    assert spec.mean() == pytest.approx(1.0, rel=0.08)  # 1/(mu - lambda)


def test_planner_scores_speculation_pairs_on_heavy_fleet():
    """On the heavy-shift fleet (static replication unaffordable at u=0.7)
    the planner must choose to clone, record the trigger on the Plan,
    and never score worse than plain replication (same CRN draws)."""
    heavy = ClusterSpec(n_workers=16, dist=ShiftedExponential(0.5, 2.0))
    planner = SimulatedPlanner(n_trials=3_000, seed=0)
    plain = planner.plan(heavy, Objective(metric="p99", utilization=0.7))
    sp = planner.plan(heavy, Objective(
        metric="p99", utilization=0.7,
        policies=tuple(
            PolicyCandidate("clone", quantile=q) for q in (0.8, 0.9)
        ),
    ))
    assert plain.policy is None
    assert sp.policy.kind == "clone" and sp.policy.quantile in (0.8, 0.9)
    assert sp.score <= plain.score


# -- deadlines / EDF ----------------------------------------------------------

def test_deadline_expired_at_admission_is_dropped():
    master = EventDrivenMaster(
        1, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=1, drop_expired=True),
    )
    dead = Request(request_id=0, arrival=1.0, deadline=0.5)
    ok = Request(request_id=1, arrival=1.0, deadline=99.0)
    master.submit(dead)
    master.submit(ok)
    jobs = master.run()
    assert dead.dropped and dead in master.dropped_requests
    assert math.isnan(dead.completion) and dead.missed_deadline
    assert len(jobs) == 1 and jobs[0].requests == (ok,)
    assert ok.completion == pytest.approx(2.0) and not ok.missed_deadline


def test_deadline_expired_while_queued_dropped_at_formation():
    master = EventDrivenMaster(
        1, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=2, drop_expired=True),
    )
    stale = Request(request_id=0, arrival=0.0, deadline=0.5)
    fresh = Request(request_id=1, arrival=1.0, deadline=99.0)
    master.submit(stale)
    master.submit(fresh)  # formation fires at t=1.0, stale already expired
    jobs = master.run()
    assert stale.dropped
    assert len(jobs) == 1 and jobs[0].size == 1


def test_missed_deadline_served_when_drop_disabled():
    master = EventDrivenMaster(
        1, lambda job, g: np.array([2.0]),
        policy=QueuePolicy(max_batch_size=1),  # drop_expired off
    )
    req = Request(request_id=0, arrival=0.0, deadline=1.0)
    master.submit(req)
    master.run()
    assert not req.dropped
    assert req.completion == pytest.approx(2.0)
    assert req.missed_deadline  # late but served


def test_edf_discipline_serves_most_urgent_batch_first():
    master = EventDrivenMaster(
        1, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=1, discipline="edf"),
    )
    deadlines = [math.inf, 5.0, 1.0, 3.0]
    for i, d in enumerate(deadlines):
        master.submit(Request(request_id=i, arrival=0.1 * i, deadline=d))
    jobs = master.run()
    served = [job.requests[0].request_id for job in jobs]
    # id 0 dispatches on the idle set at t=0; the rest queue and go EDF
    assert served == [0, 2, 3, 1]


@settings(max_examples=20)
@given(deadlines=st.lists(st.floats(0.1, 50.0), min_size=1, max_size=12))
def test_edf_ordering_property(deadlines):
    """Property: with one busy server and every request queued behind it,
    EDF serves in exactly (deadline, arrival, id) sorted order."""
    master = EventDrivenMaster(
        1, lambda job, g: np.array([1.0]),
        policy=QueuePolicy(max_batch_size=1, discipline="edf"),
    )
    master.submit(Request(request_id=999, arrival=0.0))  # occupies the set
    reqs = [
        Request(request_id=i, arrival=0.1 + 1e-3 * i, deadline=0.1 + d)
        for i, d in enumerate(deadlines)
    ]
    for r in reqs:
        master.submit(r)
    jobs = master.run()
    served = [job.requests[0].request_id for job in jobs[1:]]
    expected = [
        r.request_id
        for r in sorted(reqs, key=lambda r: (r.deadline, r.arrival))
    ]
    assert served == expected


def test_engine_deadline_telemetry_and_drop():
    """The engine threads deadlines end to end: miss rate reported, tuner
    fed, drop-on-expiry sheds dead work, sojourn stats cover survivors."""
    base = dict(
        n_server_groups=8, n_batches=4, batch_size=4, delta=0.02, mu=2.0,
        utilization=0.7, execute_model=False, seed=3,
    )
    eng = ReplicatedServingEngine(ServeEngineConfig(**base, deadline=0.4))
    out = eng.run_load(n_requests=800)
    assert 0.0 < out["deadline_miss_rate"] < 1.0
    assert eng.tuner.observed_miss_rate == pytest.approx(
        out["deadline_miss_rate"]
    )
    assert out["n_dropped"] == 0
    dropper = ReplicatedServingEngine(ServeEngineConfig(
        **base, deadline=0.05, drop_expired=True,
    ))
    out2 = dropper.run_load(n_requests=800)
    assert out2["n_dropped"] > 0
    assert out2["requests"] == 800
    dropped = [s for s in out2["stats"] if s.dropped]
    assert all(math.isnan(s.completion) for s in dropped)
    assert all(s.missed_deadline for s in dropped)
    # no-deadline runs report None, and sojourns never include dropped work
    plain = ReplicatedServingEngine(ServeEngineConfig(**base))
    assert plain.run_load(n_requests=200)["deadline_miss_rate"] is None


def test_engine_speculation_smoke():
    """Speculation knobs thread end to end: clones launch on the heavy
    fleet and per-request accounting stays consistent."""
    eng = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=16, n_batches=16, batch_size=4, delta=0.5, mu=2.0,
        utilization=0.7, execute_model=False, seed=0,
        speculation_quantile=0.8,
    ))
    out = eng.run_load(n_requests=600)
    assert out["speculations"] > 0
    assert all(s.completion >= s.dispatched >= s.arrival for s in out["stats"])


def test_simulate_sojourn_policies_bit_parity():
    """The per-B multi-candidate helper (hoisted draws) matches standalone
    calls entry for entry: 'none' is simulate_sojourn, and a clone
    trigger's lazy alternate draw is the same with or without 'none'."""
    sets = simulate_sojourn_policies(
        FLEET_DIST, N_FLEET, 4, arrival_rate=8.0,
        policies=(PolicyCandidate(), CLONE_90), n_jobs=1_500, seed=5,
    )
    plain = simulate_sojourn(
        FLEET_DIST, N_FLEET, 4, arrival_rate=8.0, n_jobs=1_500, seed=5
    )
    (spec,) = simulate_sojourn_policies(
        FLEET_DIST, N_FLEET, 4, arrival_rate=8.0, policies=(CLONE_90,),
        n_jobs=1_500, seed=5,
    )
    np.testing.assert_array_equal(sets[0], plain.samples)
    np.testing.assert_array_equal(sets[1], spec)


def test_tuner_objective_carries_speculation_triggers():
    """A load-aware re-plan must score candidate B with the SAME clone
    trigger the serving master runs — otherwise a fleet that is only
    stable because it speculates looks saturated to the planner."""
    clone = PolicyCandidate("clone", quantile=0.8)
    tuner = StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=4),
        TunerConfig(mode="simulate"),
        policy_candidates=(clone,),
    )
    tuner.observe_load(3.0)
    assert tuner.objective().policies == (PolicyCandidate(), clone)
    # without load telemetry the objective stays load-free (policy
    # scoring needs queueing), and the engine threads its config through
    fresh = StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=4),
        TunerConfig(mode="simulate"),
        policy_candidates=(clone,),
    )
    assert fresh.objective().policies is None
    eng = ReplicatedServingEngine(ServeEngineConfig(
        n_server_groups=8, n_batches=4, batch_size=2, utilization=0.7,
        execute_model=False, seed=0, speculation_quantile=0.9,
    ))
    assert eng.tuner.policy_candidates == (
        PolicyCandidate("clone", quantile=0.9),
    )


def test_engine_trace_arrival_kind_from_config():
    base = dict(n_server_groups=8, n_batches=2, batch_size=2,
                execute_model=False, seed=0, arrival_kind="trace")
    eng = ReplicatedServingEngine(ServeEngineConfig(
        **base, arrival_offsets=(0.0, 0.2, 0.5, 0.9),
    ))
    stats = eng.serve(10)  # trace cycles past its length
    assert len(stats) == 10
    with pytest.raises(ValueError, match="arrival_offsets"):
        ReplicatedServingEngine(ServeEngineConfig(**base)).serve(4)


def test_tuner_miss_rate_breach_waives_hysteresis():
    """An SLO breach (observed miss rate past target) turns the hysteresis
    threshold off: a predicted win too small to move otherwise moves."""
    rng = np.random.default_rng(0)
    dist = Exponential(mu=2.0)

    def fresh_tuner():
        t = StragglerTuner(
            ReplicationPlan(n_data=16, n_batches=16),
            TunerConfig(
                min_samples=16, cooldown_steps=0,
                improvement_threshold=0.95, miss_rate_target=0.05,
            ),
        )
        for _ in range(4):
            t.observe(dist.sample(rng, 16))
        return t

    calm = fresh_tuner()
    assert calm.maybe_replan() is None  # ~70% win < 95% threshold
    breached = fresh_tuner()
    breached.observe_deadline_misses(10, 100)
    assert breached.observed_miss_rate == pytest.approx(0.10)
    rp = breached.maybe_replan()
    assert rp is not None and rp.new_batches != 16
    breached.apply(rp)
    assert breached.observed_miss_rate is None  # window cleared on apply


# -- tuner telemetry plumbing -------------------------------------------------

def test_tuner_observe_load_and_sojourn_windows():
    tuner = StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=4),
        TunerConfig(min_samples=8, cooldown_steps=0, mode="simulate"),
    )
    assert tuner.observed_arrival_rate is None
    tuner.observe_load(2.0)
    tuner.observe_load(4.0)
    tuner.observe_load(math.inf)  # ignored
    assert tuner.observed_arrival_rate == pytest.approx(3.0)
    assert tuner.observed_sojourn("p99") is None
    tuner.observe_sojourn(np.linspace(1.0, 2.0, 100))
    assert tuner.observed_sojourn("mean") == pytest.approx(1.5)
    assert tuner.observed_sojourn("p99") == pytest.approx(1.99, abs=0.02)
    # load flows into the objective only for load-capable planners
    assert tuner.planner.consumes_load
    assert tuner.objective().arrival_rate == pytest.approx(3.0)
    analytic = StragglerTuner(
        ReplicationPlan(n_data=8, n_batches=4), TunerConfig()
    )
    analytic.observe_load(2.0)
    assert not analytic.objective().load_aware


def test_forced_move_bypasses_observed_sojourn_hysteresis():
    """A current B that is infeasible under batch_divisor forces the move
    even when the observed-sojourn baseline would never clear hysteresis."""
    rng = np.random.default_rng(0)
    tuner = StragglerTuner(
        ReplicationPlan(n_data=12, n_batches=3),  # 3 does not divide 8
        TunerConfig(min_samples=16, cooldown_steps=0, mode="simulate",
                    improvement_threshold=0.5, sim_trials=500),
        batch_divisor=8,
    )
    tuner.observe_load(4.0)  # load-aware objective
    for _ in range(8):
        tuner.observe(FLEET_DIST.sample(rng, 12))
        # observed sojourns far BELOW any prediction: a non-forced move
        # could never clear the 50% threshold against this baseline
        tuner.observe_sojourn(np.full(8, 1e-6))
    rp = tuner.maybe_replan()
    assert rp is not None
    assert rp.new_batches in (1, 2, 4)
    assert rp.predicted_old == math.inf


# -- model width knob -----------------------------------------------------


@pytest.mark.parametrize("published", [True, False])
def test_engine_model_width_follows_reduced(published):
    """``reduced=False`` serves qwen2-0.5b at its published widths (config
    only: nothing is allocated); the default stays the tiny twin."""
    from repro.launch.serve import ServeConfig

    sc = ServeEngineConfig(reduced=not published)
    cfg = sc.arch_config()
    widths = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
              cfg.d_ff, cfg.vocab_size)
    published_widths = (24, 896, 14, 2, 4864, 151936)
    assert (widths == published_widths) is published
    assert cfg.family == "dense" and cfg.qkv_bias and cfg.tie_embeddings
    assert ServeEngineConfig().reduced and ServeConfig().reduced
