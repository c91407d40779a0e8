"""Smoke test of the system's main path on a TPU.

Runs, in one process and through the entry points a user calls:

(a) planning: ``sweep_sojourn_policies`` on the fleet grid of
    ``benchmarks/bench_sweep_kernel.py`` (N=10k workers, B in {50, 100,
    200}, 4 policy kinds, J=300 jobs, K=20 bootstrap resamples) on the
    compiled Pallas kernel and on the jit+vmap backend, which must agree
    bit for bit in every cell; a few cells of every Pallas dispatch
    against the numpy reference on the same cell tensors; then one
    ``SimulatedPlanner.plan`` with coded candidates on the Pallas backend.
(b) serving: a ``ReplicatedServingEngine`` at the published qwen2-0.5b
    widths (random weights from ``--seed``) answers 32 requests; every
    request's tokens must equal a direct jitted greedy prefill/decode loop
    on the same parameters, and the first batch's prefill logits must
    match a float32 evaluation on the host CPU.

``--chips 4`` runs only the sharded fleet sweep (K=256 and K=255
resamples over a 4-device ``cells`` mesh) against the same sweep on one
chip.  All data comes from ``--seed``.  The script exits non-zero, and
prints no result, when JAX sees no TPU.  Its last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Usage: python chip_smoke.py [--seed 0] [--chips 1|4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

# fleet grid of benchmarks/bench_sweep_kernel.py
N_WORKERS = 10_000
SPLITS = (50, 100, 200)
N_JOBS = 300
ARRIVAL_RATE = 40.0
N_ATOMS = 10_000
K_REPLAN = 20
K_FLEET = 256
# numpy reference vs device, as tests/test_sojourn_kernel.py compares them
REF_RTOL = 1e-5
# bf16 serving vs float32 on the host CPU.  Every layer rounds the
# residual stream and each matmul operand to bf16 (relative step 2**-8), so
# the final hidden state drifts by a few percent rms, and the largest of
# the 4 x 151936 logit deviations sits ~5 rms out.  A 24-layer reduced-width
# twin on the CPU backend drifts 1.7% rms with a max of 0.085 std; the
# limits leave 3x headroom and still fail any wrong weight, layout or mask,
# which moves the logits by O(1) std.
LOGIT_ATOL_REL = 0.25  # max |err| / std(f32 logits)
LOGIT_RMS_REL = 0.05  # rms(err) / rms(f32 logits)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what) -> None:
    """Fail the run (unlike ``assert``, this survives ``python -O``)."""
    if not ok:
        raise AssertionError(what)


_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading a
    compiled program from the persistent cache), and persistent-cache
    hits.  Traces of nested jitted functions nest, so the clock keeps each
    event's interval and counts the length of their union."""

    def __init__(self):
        import jax

        self._spans: list[tuple[float, float]] = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            end = time.perf_counter()
            self._spans.append((end - secs, end))

    @property
    def seconds(self) -> float:
        total, reach = 0.0, -np.inf
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def timed(self, label, fn, *args, **kwargs):
        """Run ``fn`` to completion on the device; log its wall time split
        into compile and the rest."""
        import jax

        c0, t0 = self.seconds, time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        wall = time.perf_counter() - t0
        comp = self.seconds - c0
        log(f"[{label}] wall {wall:.3f}s = compile {comp:.3f}s + run "
            f"{wall - comp:.3f}s")
        return out


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def fleet_resamples(seed: int, k: int, n_atoms: int = N_ATOMS):
    from repro.core.order_stats import Empirical

    rng = np.random.default_rng(seed)
    pool = rng.gamma(2.0, 0.5, n_atoms)
    return [Empirical(rng.choice(pool, pool.size)) for _ in range(k)]


def fleet_sweep(dists, backend, mesh=None, *, n_workers=N_WORKERS,
                splits=SPLITS, n_jobs=N_JOBS):
    from repro.core.policies import PolicyCandidate
    from repro.core.simulator import sweep_sojourn_policies

    return sweep_sojourn_policies(
        dists,
        n_workers=n_workers,
        arrival_rate=ARRIVAL_RATE,
        policies=(
            PolicyCandidate("none"),
            PolicyCandidate("clone", quantile=0.85),
            PolicyCandidate("relaunch", quantile=0.9),
            PolicyCandidate("hedged", hedge_fraction=0.3),
        ),
        n_jobs=n_jobs,
        seed=3,
        feasible_b=list(splits),
        backend=backend,
        mesh=mesh,
    )


def _same(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    bad = int(np.sum(a != b)) if a.shape == b.shape else "shape"
    check(a.shape == b.shape and np.array_equal(a, b),
          f"{what}: not bitwise equal ({bad} differ)")


def _record_dispatches(ss):
    """Wrap the sweep's kernel seam so the cell tensors of every dispatch
    are kept for the reference check."""
    calls = []
    real = ss.sojourn_policy_cells

    def recording(*args, **kwargs):
        out = real(*args, **kwargs)
        calls.append((args, np.asarray(out[0]), np.asarray(out[1])))
        return out

    ss.sojourn_policy_cells = recording
    return calls, lambda: setattr(ss, "sojourn_policy_cells", real)


def phase_planning(clock, seed, *, k=K_REPLAN, n_workers=N_WORKERS,
                   splits=SPLITS, n_jobs=N_JOBS, n_atoms=N_ATOMS):
    from repro.core import (
        ClusterSpec,
        CodingCandidate,
        Objective,
        ShiftedExponential,
        SimulatedPlanner,
    )
    from repro.kernels import sojourn_sweep as ss
    from repro.kernels.sojourn_sweep.ref import sojourn_cells_reference

    dists = fleet_resamples(seed, k, n_atoms)
    grid = dict(n_workers=n_workers, splits=splits, n_jobs=n_jobs)
    calls, restore = _record_dispatches(ss)
    try:
        res_pl = clock.timed("a/sweep pallas cold", fleet_sweep, dists,
                             "pallas", **grid)
    finally:
        restore()
    clock.timed("a/sweep pallas warm", fleet_sweep, dists, "pallas", **grid)
    clock.timed("a/sweep jax cold", fleet_sweep, dists, "jax", **grid)
    res_jx = clock.timed("a/sweep jax warm", fleet_sweep, dists, "jax",
                         **grid)
    check(res_pl.backend == "pallas" and res_jx.backend == "jax",
          f"backends ran: {res_pl.backend}, {res_jx.backend}")
    _same(res_pl.samples, res_jx.samples, "pallas vs jax sojourns")
    _same(res_pl.extra_fraction, res_jx.extra_fraction,
          "pallas vs jax extra dispatches")
    log(f"[a] pallas == jax bitwise in all {res_pl.samples.shape[:3]} "
        f"(dist, B, policy) cells x {res_pl.samples.shape[3]} jobs")

    n_checked = 0
    for args, out, extra in calls:
        arr, svc, alt, kinds, thr, hm, ng = args
        f32 = np.float32
        for c in sorted({0, svc.shape[0] - 1}):
            r_out, r_extra = sojourn_cells_reference(
                np.asarray(arr, f32), np.asarray(svc[c:c + 1], f32),
                np.asarray(alt[c:c + 1], f32), kinds,
                np.asarray(thr[c:c + 1], f32), hm, ng[c:c + 1])
            np.testing.assert_allclose(out[c], r_out[0], rtol=REF_RTOL)
            np.testing.assert_allclose(extra[c], r_extra[0], rtol=REF_RTOL)
            n_checked += len(kinds)
    log(f"[a] {n_checked} (cell, policy) pairs of {len(calls)} pallas "
        f"dispatches match the numpy reference (rtol {REF_RTOL})")

    planner = SimulatedPlanner(n_trials=6_000, seed=seed, backend="pallas")
    plan = clock.timed(
        "a/plan coded", planner.plan,
        ClusterSpec(n_workers=16, dist=ShiftedExponential(delta=0.05, mu=2.0)),
        Objective(metric="mean",
                  coding=tuple(CodingCandidate("mds", s) for s in (4, 8, 12))),
    )
    check(plan.backend == "pallas", f"Plan.backend is {plan.backend!r}")
    coding = plan.coding.describe() if plan.coding is not None else "none"
    log(f"[a] Plan.backend={plan.backend} B={plan.n_batches} "
        f"coding={coding} predicted_mean={plan.predicted.mean!r}")


def _greedy(prefill_fn, decode_fn, params, prompts, gen_tokens):
    import jax.numpy as jnp

    logits, state = prefill_fn(params, {"tokens": prompts})
    first_logits = logits
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = [tok]
    for i in range(gen_tokens - 1):
        logits, state = decode_fn(params, state, tok,
                                  jnp.int32(prompts.shape[1] + i))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(tok)
    return np.asarray(jnp.concatenate(out, axis=1)), first_logits


def _check_logits(dev_logits, ref_logits):
    dev = np.asarray(dev_logits, np.float64)[:, -1]
    ref = np.asarray(ref_logits, np.float64)[:, -1]
    err = np.abs(dev - ref)
    atol = LOGIT_ATOL_REL * float(ref.std())
    rms_rel = float(np.sqrt(np.mean(err ** 2) / np.mean(ref ** 2)))
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    dev_top, ref_top = dev.argmax(-1), ref.argmax(-1)
    # top-1 must agree wherever the f32 top-2 margin exceeds twice the
    # observed error; closer rows are ties at bf16
    decided = margin > 2 * err.max()
    log(f"[b] prefill logits vs CPU f32: max|err| {err.max()!r} "
        f"(limit {atol!r}), rms rel {rms_rel!r} (limit {LOGIT_RMS_REL}), "
        f"top-1 equal {int(np.sum(dev_top == ref_top))}/{len(ref)} "
        f"({int(decided.sum())} decided), f32 top-2 margins "
        f"{margin.tolist()}")
    check(np.all(np.isfinite(dev)), "non-finite device logits")
    check(err.max() <= atol, f"max |err| {err.max()!r} > {atol!r}")
    check(rms_rel <= LOGIT_RMS_REL, f"rms rel err {rms_rel!r}")
    check(np.all(dev_top[decided] == ref_top[decided]),
          f"top-1 {dev_top} != {ref_top} (margins {margin})")


def phase_serving(clock, seed, *, reduced=False, n_requests=32, batch=4,
                  prompt_len=128, gen_tokens=32, max_len=256):
    import jax

    from repro.models import decode_step, prefill
    from repro.serving import ReplicatedServingEngine, ServeEngineConfig

    sc = ServeEngineConfig(
        arch="qwen2-0.5b", reduced=reduced, execute_model=True,
        planner_mode="simulate", sim_backend="pallas", plan_initial=True,
        utilization=0.5, batch_size=batch, prompt_len=prompt_len,
        gen_tokens=gen_tokens, max_len=max_len, seed=seed,
    )
    engine = clock.timed("b/engine init", ReplicatedServingEngine, sc)
    cfg = engine.cfg
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    log(f"[b] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {n_params} parameters; initial plan "
        f"B={engine.plan.n_batches} (sweep backend {engine.planner.backend})")
    stats = clock.timed("b/serve", engine.serve, n_requests)
    check(len(stats) == n_requests, f"{len(stats)} of {n_requests} answered")
    stats = sorted(stats, key=lambda s: s.request_id)
    for s in stats:
        check(not s.dropped and s.tokens.shape == (gen_tokens,),
              f"request {s.request_id}: dropped={s.dropped}, tokens "
              f"{s.tokens.shape}")

    prefill_fn = jax.jit(lambda p, b: prefill(cfg, engine.shard, p, b,
                                              max_len=max_len))
    decode_fn = jax.jit(lambda p, s, t, c: decode_step(cfg, engine.shard, p,
                                                       s, t, c))
    first = None
    for lo in range(0, n_requests, batch):
        job = stats[lo:lo + batch]
        # FIFO formation: consecutive request ids ride one batch job
        check(len({(s.dispatched, s.completion) for s in job}) == 1,
              f"requests {[s.request_id for s in job]} were not served as "
              "one batch")
        # the engine draws each request's prompt from its prompt key folded
        # with the request id, whatever batch the request rides in
        prompts = jax.numpy.stack([
            jax.random.randint(
                jax.random.fold_in(engine._prompt_key, s.request_id),
                (prompt_len,), 0, cfg.vocab_size)
            for s in job
        ])
        tokens, logits = clock.timed(f"b/direct greedy {lo // batch}",
                                     _greedy, prefill_fn, decode_fn,
                                     engine.params, prompts, gen_tokens)
        for k, s in enumerate(job):
            _same(s.tokens, tokens[k], f"request {s.request_id} tokens")
        if first is None:
            first = (prompts, logits)
    log(f"[b] all {n_requests} requests answered with {gen_tokens} tokens, "
        f"equal to the direct greedy loop")

    cpu = jax.devices("cpu")[0]
    params32 = jax.device_put(
        jax.tree.map(lambda x: np.asarray(x, np.float32), engine.params), cpu)
    ref_logits, _ = clock.timed(
        "b/cpu f32 prefill", prefill_fn, params32,
        {"tokens": jax.device_put(np.asarray(first[0]), cpu)})
    _check_logits(first[1], ref_logits)


def phase_sharded(clock, seed, devices, *, ks=(K_FLEET, K_FLEET - 1),
                  n_atoms=N_ATOMS, **grid):
    from repro.kernels.sojourn_sweep import cells_mesh

    mesh = cells_mesh(devices)
    for k in ks:
        dists = fleet_resamples(seed, k, n_atoms)
        one = clock.timed(f"c/K={k} one chip", fleet_sweep, dists, "jax",
                          **grid)
        many = clock.timed(f"c/K={k} {len(devices)} chips", fleet_sweep,
                           dists, "jax", mesh, **grid)
        clock.timed(f"c/K={k} {len(devices)} chips warm", fleet_sweep, dists,
                    "jax", mesh, **grid)
        _same(many.samples, one.samples, f"K={k} sharded vs one-chip sweep")
        _same(many.extra_fraction, one.extra_fraction,
              f"K={k} sharded vs one-chip extra dispatches")
        pad = (-k) % len(devices)
        log(f"[c] K={k}: {len(devices)}-chip cells mesh == one chip bitwise "
            f"({k} cells per dispatch, {pad} padding cells)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"{len(devices)} visible", file=sys.stderr)
        return 1
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(clock, args.seed, devices[:4])
    else:
        with jax.default_device(dev):
            phase_planning(clock, args.seed)
            log(f"[a] peak_bytes_in_use {_peak_bytes(dev)}")
            phase_serving(clock, args.seed)
            log(f"[b] peak_bytes_in_use {_peak_bytes(dev)}")
    log(f"total wall {time.perf_counter() - t0:.3f}s, of which compile "
        f"{clock.seconds:.3f}s; persistent cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
