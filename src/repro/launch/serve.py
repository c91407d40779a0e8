"""Batched serving driver with replicated request dispatch.

Serving maps the paper one-to-one: requests batches = the paper's data
batches, server groups = workers, and REPLICATING a request batch to r
server groups lets the master take the FIRST response per batch — the
paper's max-min completion applied to tail latency ('the tail at scale').

The driver (a) actually runs prefill + decode to produce tokens (on the
reduced model by default, at published widths with ``--full-width``),
and (b) simulates the latency of a fleet of N server groups under
the calibrated straggler model, BOTH as per-round batch-completion time
(the serving twin of Fig. 2) and as per-request SOJOURN under Poisson
arrivals at the configured utilization (the queueing-aware mode of
core.simulator) — showing how the latency-optimal B moves once real
traffic queues.

Run: PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --tokens 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, reduced_config
from repro.core import (
    ClusterSpec,
    Objective,
    PolicyCandidate,
    ReplicationPlan,
    ShiftedExponential,
    SimulatedPlanner,
    sweep_simulated,
)
from repro.models import Shard, decode_step, init_params, prefill

__all__ = ["ServeConfig", "run_serving"]


@dataclasses.dataclass
class ServeConfig:
    arch: str = "qwen2-0.5b"
    reduced: bool = True  # False serves the published widths of ``arch``
    batch: int = 4
    prompt_len: int = 32
    gen_tokens: int = 16
    max_len: int = 128
    seed: int = 0
    # latency sim
    n_servers: int = 16
    n_batches: int = 4
    delta: float = 0.05
    mu: float = 20.0
    # offered load for the queueing-aware (sojourn) sweep
    utilization: float = 0.7
    # straggler-policy portfolio offered to the load-aware planner: clone /
    # relaunch triggers at these late-quantiles plus hedged dispatch at
    # these tail fractions (a plain-replication 'none' candidate is always
    # in the race); the plan reports the winning candidate on Plan.policy
    speculation_quantiles: tuple[float, ...] = (0.8, 0.9, 0.95)
    hedge_fractions: tuple[float, ...] = (0.1, 0.3)

    def policy_candidates(self) -> tuple[PolicyCandidate, ...]:
        return (
            *(
                PolicyCandidate("clone", quantile=q)
                for q in self.speculation_quantiles
            ),
            *(
                PolicyCandidate("relaunch", quantile=q)
                for q in self.speculation_quantiles
            ),
            *(
                PolicyCandidate("hedged", hedge_fraction=f)
                for f in self.hedge_fractions
            ),
        )


def run_serving(sc: ServeConfig):
    cfg = get_config(sc.arch)
    if sc.reduced:
        cfg = reduced_config(cfg)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(sc.seed), cfg
    )
    shard = Shard.local()
    key = jax.random.PRNGKey(sc.seed + 1)
    prompts = jax.random.randint(
        key, (sc.batch, sc.prompt_len), 0, cfg.vocab_size
    )
    t0 = time.time()
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            key, (sc.batch, cfg.n_patches, cfg.frontend_dim)
        )
    logits, state = jax.jit(
        lambda p, b: prefill(cfg, shard, p, b, max_len=sc.max_len)
    )(params, batch)
    logits.block_until_ready()
    prefill_s = time.time() - t0

    step = jax.jit(
        lambda p, s, t, c: decode_step(cfg, shard, p, s, t, c)
    )
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    base = sc.prompt_len + (cfg.n_patches if cfg.family == "vlm" else 0)
    t0 = time.time()
    for i in range(sc.gen_tokens - 1):
        logits, state = step(params, state, tok, jnp.int32(base + i))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    decode_s = time.time() - t0
    generated = jnp.concatenate(out_tokens, axis=1)

    # latency across the diversity-parallelism spectrum: ONE batched
    # CRN sweep (each cell bit-identical to a standalone simulate_maxmin)
    dist = ShiftedExponential(delta=sc.delta, mu=sc.mu)
    res = sweep_simulated(dist, sc.n_servers, n_trials=20_000, seed=7)
    lat = {p.n_batches: {"mean": p.mean, "p99": p.p99} for p in res.points}
    # ... and the queueing twin: per-request sojourn under Poisson arrivals
    # at the configured utilization, scored through the load-aware planner
    # offering the full straggler-policy portfolio (clone / relaunch /
    # hedged / plain).  ONE sweep covers everything: all candidates of one
    # B share one CRN draw set, so each reported B carries its best policy
    # and the winner on Plan.policy says which mitigation — if any — beat
    # static replication
    spec = ClusterSpec(n_workers=sc.n_servers, dist=dist)
    plan = SimulatedPlanner(n_trials=20_000, seed=7).plan(
        spec,
        Objective(
            metric="p99",
            utilization=sc.utilization,
            policies=sc.policy_candidates(),
        ),
    )
    sojourn = {
        p.n_batches: {"mean": p.mean, "p99": p.p99, "p999": p.p999}
        for p in plan.spectrum.points
    }
    return {
        "generated": np.asarray(generated),
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "latency_by_B": lat,
        "sojourn_by_B": sojourn,
        "sojourn_best_B": plan.n_batches,
        "policy": plan.policy,
        "speculative_p99": plan.score,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--full-width", action="store_true",
                    help="serve the published widths instead of the "
                         "reduced model")
    args = ap.parse_args()
    use_compile_cache()
    out = run_serving(ServeConfig(arch=args.arch, gen_tokens=args.tokens,
                                  batch=args.batch,
                                  reduced=not args.full_width))
    print(f"prefill {out['prefill_s']*1e3:.1f}ms, "
          f"decode {out['decode_s']*1e3:.1f}ms for {args.tokens} tokens")
    print("generated tokens[0,:8]:", out["generated"][0, :8])
    print("batch-latency vs B (simulated fleet):")
    for b, d in out["latency_by_B"].items():
        print(f"  B={b:3d}  mean={d['mean']*1e3:7.2f}ms  p99={d['p99']*1e3:7.2f}ms")
    print("request sojourn vs B (Poisson arrivals; best policy per B):")
    for b, d in out["sojourn_by_B"].items():
        print(f"  B={b:3d}  mean={d['mean']*1e3:7.2f}ms  p99={d['p99']*1e3:7.2f}ms"
              f"  p999={d['p999']*1e3:7.2f}ms")
    pol = out["policy"]
    if pol is not None and pol.enabled:
        what = {
            "clone": f"clone at the q={pol.quantile:g} late-quantile",
            "relaunch": f"relaunch at the q={pol.quantile:g} late-quantile",
            "hedged": f"hedged dispatch of {pol.hedge_fraction:.0%} of jobs",
        }[pol.kind]
    else:
        what = "plain replication (no mitigation candidate pays off)"
    print(
        f"load-aware p99-optimal B* = {out['sojourn_best_B']}: {what} "
        f"(predicted p99 {out['speculative_p99']*1e3:.2f}ms)"
    )


if __name__ == "__main__":
    main()
