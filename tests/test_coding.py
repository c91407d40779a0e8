"""Coded computation as a first-class Plan alternative (PR 9).

Three layers of pins:

* **algebra** — property tests (``_prop`` shim): MDS / polynomial-coded
  matmul decode EXACTLY from ANY k-of-n completion subset, and the cyclic
  code's decode weights reconstruct the uniform batch sum for EVERY
  tolerable erasure pattern (exhaustive over small fleets).
* **statistics** — ``expected_kofn_time`` closed form vs Monte-Carlo for
  Exp/SExp at several (N, s); candidate/objective validation.
* **decision** — the planner races coded candidates against every feasible
  replication split on shared CRN draws: heavy-tail fleets adopt coding,
  light-tail fleets keep replication (the Peng/Soljanin/Whiting flip),
  measured overheads are charged, and provenance lands on the Plan.
"""

import itertools

import numpy as np
import pytest
from _prop import given, settings, st

from repro.core import (
    ClusterSpec,
    CodingCandidate,
    CyclicGradientCode,
    Exponential,
    MDSCode,
    Objective,
    PolynomialMatmulCode,
    ShiftedExponential,
    chebyshev_nodes,
    expected_kofn_time,
    make_planner,
    simulate_gradient_coding,
    sweep_coded,
)
from repro.core.planner import AnalyticPlanner, EmpiricalPlanner
from repro.core.order_stats import Empirical


# ---------------------------------------------------------------- algebra --
@settings(deadline=None, max_examples=20)
@given(
    n=st.integers(2, 10),
    k=st.integers(1, 10),
    width=st.integers(1, 6),
    seed=st.integers(0, 500),
)
def test_mds_decodes_from_any_k_subset(n, k, width, seed):
    """ANY k of the n coded blocks recover the data exactly (Vandermonde
    at distinct Chebyshev nodes: every k-row minor is invertible)."""
    if k > n:
        return
    rng = np.random.default_rng(seed)
    code = MDSCode(n=n, k=k)
    blocks = rng.standard_normal((k, width))
    coded = code.encode(blocks)
    alive = np.zeros(n, dtype=bool)
    alive[rng.choice(n, size=k, replace=False)] = True
    out = code.decode(coded[alive], alive)
    np.testing.assert_allclose(out, blocks, atol=1e-6)


@settings(deadline=None, max_examples=15)
@given(
    m=st.integers(1, 3),
    p=st.integers(1, 3),
    extra=st.integers(0, 3),
    seed=st.integers(0, 500),
)
def test_poly_matmul_decodes_from_any_k_subset(m, p, extra, seed):
    """Polynomial-coded matmul: any k = m*p worker products interpolate
    the full A @ B.T exactly."""
    n_workers = m * p + extra
    rng = np.random.default_rng(seed)
    code = PolynomialMatmulCode(m=m, p=p, n_workers=n_workers)
    a = rng.standard_normal((m * 2, 4))
    b = rng.standard_normal((p * 3, 4))
    enc_a, enc_b = code.encode_a(a), code.encode_b(b)
    products = np.stack(
        [code.worker_product(enc_a[i], enc_b[i]) for i in range(n_workers)]
    )
    alive = np.zeros(n_workers, dtype=bool)
    alive[rng.choice(n_workers, size=code.k, replace=False)] = True
    out = code.decode(products[alive], alive)
    np.testing.assert_allclose(out, a @ b.T, atol=1e-5)


@pytest.mark.parametrize("n,s", [(4, 1), (5, 2), (6, 2)])
def test_cyclic_decodes_every_tolerable_erasure(n, s):
    """EXHAUSTIVE over erasure patterns: every (N-s)-subset of workers
    yields weights that reconstruct the all-ones combination row."""
    code = CyclicGradientCode(n_workers=n, s=s)
    b = code.coefficients()
    for alive_idx in itertools.combinations(range(n), n - s):
        alive = np.zeros(n, dtype=bool)
        alive[list(alive_idx)] = True
        w = code.decode_weights(alive)
        assert w is not None, alive_idx
        np.testing.assert_allclose(b[alive].T @ w, 1.0, atol=1e-6)


def test_mds_undecodable_below_k():
    code = MDSCode(n=6, k=4)
    alive = np.array([True, True, True, False, False, False])
    assert code.decode_weights(alive) is None
    with pytest.raises(ValueError, match="undecodable"):
        code.decode(np.zeros((3, 2)), alive)


def test_chebyshev_nodes_distinct():
    x = chebyshev_nodes(32)
    assert np.unique(x).size == 32
    assert np.all(np.abs(x) < 1.0)


# ------------------------------------------------------------- validation --
def test_candidate_validation():
    with pytest.raises(ValueError, match="scheme"):
        CodingCandidate(scheme="raptor", s=1)
    with pytest.raises(ValueError, match="non-negative"):
        CodingCandidate(scheme="mds", s=-1)
    with pytest.raises(ValueError, match="finite"):
        CodingCandidate(scheme="mds", s=1, encode_overhead=-0.5)
    c = CodingCandidate(scheme="cyclic", s=3)
    with pytest.raises(ValueError, match="tolerates every worker"):
        c.k(3)
    assert c.k(8) == 5 and c.load(8) == 4.0
    assert not c.resolved and c.total_overhead == 0.0
    r = CodingCandidate("mds", 4, encode_overhead=0.1, decode_overhead=0.2)
    assert r.resolved and abs(r.total_overhead - 0.3) < 1e-12
    assert r.load(12) == pytest.approx(12 / 8)


def test_objective_coding_validation():
    with pytest.raises(ValueError, match="non-empty"):
        Objective(coding=())
    with pytest.raises(TypeError, match="CodingCandidate"):
        Objective(coding=("cyclic",))
    obj = Objective(coding=[CodingCandidate("mds", 2)])
    assert isinstance(obj.coding, tuple)


def test_analytic_planner_rejects_coding_loudly():
    spec = ClusterSpec(n_workers=8, dist=Exponential(1.0))
    obj = Objective(metric="mean", coding=(CodingCandidate("mds", 2),))
    with pytest.raises(ValueError, match="[Ss]imulated"):
        AnalyticPlanner().plan(spec, obj)


# ------------------------------------------------------------- statistics --
@pytest.mark.parametrize(
    "dist", [Exponential(mu=2.0), ShiftedExponential(delta=0.1, mu=1.5)],
    ids=["exp", "sexp"],
)
@pytest.mark.parametrize("n,s", [(8, 0), (8, 3), (12, 6)])
def test_expected_kofn_closed_form_matches_mc(dist, n, s):
    """The k-of-n closed form is the mean the coded simulator converges to
    (cyclic geometry: k = N-s at load s+1 — expected_coding_time's twin)."""
    mc = simulate_gradient_coding(dist, n, s, n_trials=100_000, seed=s)
    cf = expected_kofn_time(dist, n, n - s, load=float(s + 1))
    assert abs(mc.mean - cf) < 5 * mc.stderr + 1e-3


def test_expected_kofn_rejects_empirical():
    emp = Empirical(np.random.default_rng(0).exponential(1.0, 100))
    with pytest.raises(TypeError, match="sweep_coded"):
        expected_kofn_time(emp, 8, 4)


def test_sweep_coded_charges_measured_overhead():
    """None overheads are MEASURED by the planner; the resolved candidate
    lands on the Plan with both halves filled and its predicted completion
    strictly above the free-coding prediction."""
    spec = ClusterSpec(n_workers=16, dist=ShiftedExponential(0.05, 2.0))
    planner = make_planner("simulate", n_trials=2_000, seed=0)
    free = planner.plan(spec, Objective(metric="mean", coding=(
        CodingCandidate("mds", 12, encode_overhead=0.0,
                        decode_overhead=0.0),)))
    measured = planner.plan(spec, Objective(metric="mean", coding=(
        CodingCandidate("mds", 12),)))
    assert free.coding is not None and measured.coding is not None
    assert measured.coding.resolved
    assert measured.coding.encode_overhead >= 0.0
    assert measured.coding.decode_overhead > 0.0
    assert measured.predicted.mean >= free.predicted.mean


# --------------------------------------------------------------- decision --
_HEAVY = ShiftedExponential(delta=0.05, mu=2.0)  # massless-ish shift: coded
_LIGHT = Exponential(mu=2.0)  # memoryless: replication (B=1) wins
_CANDS = tuple(
    CodingCandidate("mds", s, encode_overhead=1e-4, decode_overhead=1e-4)
    for s in (4, 8, 12)
)


def test_planner_adopts_coding_on_heavy_tail():
    spec = ClusterSpec(n_workers=16, dist=_HEAVY)
    plan = make_planner("simulate", n_trials=4_000, seed=1).plan(
        spec, Objective(metric="mean", coding=_CANDS)
    )
    assert plan.coding is not None and plan.coding.scheme == "mds"
    # coded plans carry no replication-side speculation decisions
    assert plan.policy is None
    # and beat every pure-replication split on the shared draws
    assert plan.predicted.mean < min(p.mean for p in plan.spectrum.points)


def test_planner_keeps_replication_on_light_tail():
    spec = ClusterSpec(n_workers=16, dist=_LIGHT)
    plan = make_planner("simulate", n_trials=4_000, seed=1).plan(
        spec, Objective(metric="mean", coding=_CANDS)
    )
    assert plan.coding is None
    assert plan.n_batches == 1  # the paper's light-tail optimum


def test_empirical_planner_coded_vote_gate():
    """Bootstrap planner: coding must win the POOLED metric AND a majority
    of resamples; on heavy-tail data it does, and the vote becomes the
    plan confidence."""
    rng = np.random.default_rng(3)
    samples = _HEAVY.sample(rng, 4_000)
    spec = ClusterSpec(n_workers=16, dist=Empirical(samples))
    planner = EmpiricalPlanner(n_trials=1_500, n_resamples=10, seed=2)
    plan = planner.plan(spec, Objective(metric="mean", coding=_CANDS))
    assert plan.coding is not None
    assert plan.confidence is not None and plan.confidence > 0.5


def test_plan_coding_backend_provenance():
    """A pallas-backed coded sweep stamps the resolved engine on the Plan."""
    spec = ClusterSpec(n_workers=12, dist=_HEAVY)
    plan = make_planner("simulate", n_trials=1_000, seed=0,
                        backend="pallas").plan(
        spec, Objective(metric="mean", coding=_CANDS[:1])
    )
    assert plan.backend == "pallas"


@pytest.mark.slow
def test_crossover_majority_across_seeds():
    """The Peng/Soljanin/Whiting flip, pinned as a majority across seeds:
    heavy-tail fleets adopt a coded scheme, light-tail fleets keep
    replication — on the same candidate set and trial budget."""
    heavy_wins = light_keeps = 0
    seeds = range(5)
    for seed in seeds:
        planner = make_planner("simulate", n_trials=6_000, seed=seed)
        ph = planner.plan(ClusterSpec(n_workers=16, dist=_HEAVY),
                          Objective(metric="mean", coding=_CANDS))
        pl = planner.plan(ClusterSpec(n_workers=16, dist=_LIGHT),
                          Objective(metric="mean", coding=_CANDS))
        heavy_wins += ph.coding is not None
        light_keeps += pl.coding is None
    assert heavy_wins > len(seeds) / 2, heavy_wins
    assert light_keeps > len(seeds) / 2, light_keeps
