"""Plain numpy reference of the served engine's initial plan.

With ``planner_mode="simulate"``, offered load and no straggler
mitigation, the engine's planner scores every split ``B`` of its
``N = n_server_groups`` replica groups by the mean simulated sojourn of a
batch job and starts on the best one.  This follows those documented
semantics step by step and imports nothing of the program under test:

1. a job is one batch of ``batch_size`` requests and carries
   ``batch_size * (prompt_len + gen_tokens) * work_per_token`` units of
   work; a worker serves one unit in ``delta + Exp(1) / mu`` (the shifted
   exponential of the paper), so a job in ``(delta + E / mu) * work``;
2. the offered job rate holds ``utilization`` of the no-replication
   capacity: ``rate = utilization * N / (work * (delta + 1 / mu))``;
3. one shared draw stream, ``numpy.random.default_rng(planner_seed)``:
   ``plan_trials`` unit exponentials for the Poisson arrival gaps first,
   then the ``(plan_trials, N)`` matrix of service draws;
4. at split ``B`` a replica set of ``r = N / B`` contiguous workers serves
   a job in the minimum of its members' times;
5. FIFO over the ``B`` sets: job ``i`` starts on the earliest-free set
   (ties to the lowest index) at ``max(arrival, free time)``; its sojourn
   is its completion less its arrival, and the first tenth of the jobs is
   warm-up;
6. the plan is the ``B`` with the lowest mean sojourn.
"""

from __future__ import annotations

import numpy as np


def splits(n: int) -> list[int]:
    return [b for b in range(1, n + 1) if n % b == 0]


def job_work(deployment: dict, traffic: dict) -> float:
    law = deployment["service_law"]
    return (traffic["batch_size"] * (traffic["prompt_len"]
                                     + traffic["gen_tokens"])
            * law["work_per_token"])


def draws(deployment: dict, traffic: dict, planner_seed: int):
    """Arrival times ``(J,)`` and unit draws ``(J, N)`` of step 3."""
    law, n = deployment["service_law"], deployment["n_server_groups"]
    n_jobs = deployment["plan_trials"]
    work = job_work(deployment, traffic)
    rate = traffic["utilization"] * n / (work * (law["delta"]
                                                 + 1.0 / law["mu"]))
    rng = np.random.default_rng(planner_seed)
    arrivals = np.cumsum(rng.standard_exponential(n_jobs)) / rate
    return arrivals, rng.standard_exponential((n_jobs, n))


def fifo_sojourns(arrivals, svc, dtype=np.float64) -> np.ndarray:
    """Step 5, computed throughout in ``dtype``."""
    arrivals = np.asarray(arrivals).astype(dtype)
    svc = np.asarray(svc).astype(dtype)
    free = np.zeros(svc.shape[1], dtype=dtype)
    out = np.empty(len(arrivals), dtype=dtype)
    for i, a in enumerate(arrivals):
        g = int(np.argmin(free))
        done = max(a, free[g]) + svc[i, g]
        free[g] = done
        out[i] = done - a
    return out.astype(np.float64)


def sweep(deployment: dict, traffic: dict, planner_seed: int,
          dtype=np.float64) -> np.ndarray:
    """Post-warm-up sojourns ``(len(splits), J - J // 10)`` of every split."""
    law, n = deployment["service_law"], deployment["n_server_groups"]
    arrivals, unit = draws(deployment, traffic, planner_seed)
    core = (law["delta"] + unit / law["mu"]) * job_work(deployment, traffic)
    warm = unit.shape[0] // 10
    return np.stack([
        fifo_sojourns(arrivals, core.reshape(len(core), b, n // b).min(axis=2),
                      dtype)[warm:]
        for b in splits(n)])


def plan_choice(samples, n: int) -> int:
    """Step 6: the split whose samples have the lowest mean."""
    means = np.asarray(samples, dtype=np.float64).mean(axis=-1)
    return splits(n)[int(np.argmin(means))]
