"""``correct`` must come out false for the control and for each fault the
cell can have, on the CPU at a tiny size with the rest of a run driven as
on the chip (the harness's look for a chip skipped).

The control: the qwen2 reference with fp8 weights choosing the tokens,
and the planner reference computed in bfloat16 in the initial-plan
sweep's place.  Faults, planted in the program: a served token altered
where it is produced; half of the batch left out and the rest copied in
its place; a decode step that returns its cache unchanged; and in the
initial-plan sweep, one split's sojourns altered where they are produced,
and a queue recursion that leaves its state (the groups' free times)
unchanged.  No cell runs across chips, so the exchange between chips has
no fault here.
"""

import numpy as np
import pytest

from harness import runner, spec

import tiny

@pytest.fixture(autouse=True)
def _tiny_model(monkeypatch):
    tiny.serve_this_model(monkeypatch, "qwen2-0.5b.chat")


def _run(name, driver=None, seed=2**31 + 5):
    cell = spec.load_cell(name).replace(**tiny.overrides(name))
    return runner.run_cell(cell, seed=seed, seconds=1.0, traced=False,
                           start=0.0, require_chip=False, driver=driver)


class _Control:
    """The cell's driver with the control in the program's place."""

    def __init__(self, name, control):
        cell = spec.load_cell(name)
        self.mod = spec.load_module(f"drivers/{cell.traffic['driver']}.py")
        self.control = control

    def __getattr__(self, attr):
        return getattr(self.mod, attr)

    def check(self, st, run):
        return self.mod.check(st, run, control=self.control)


def test_sound_run_is_correct():
    res = _run("qwen2-0.5b.chat")
    assert res["correct"] is True, res["checks"]


def test_fp8_control_is_not_correct():
    name = "qwen2-0.5b.chat"
    res = _run(name, driver=_Control(name, "fp8"))
    assert res["correct"] is False, res["checks"]
    checks = res["checks"]
    # each part of the control fails on its own
    assert checks["served_logit_gap"]["value"] > checks["served_logit_gap"][
        "limit"]
    assert checks["plan_mean_gap"]["value"] > checks["plan_mean_gap"]["limit"]


def _break_plan_sweep(monkeypatch, fault):
    import repro.core.simulator as simulator

    real = simulator._sojourn_recursion

    def broken(arrivals, svc, n_groups):
        if fault == "unchanged":
            # every job starts at its arrival: the free times never move
            return svc[np.arange(len(arrivals)), 0].copy()
        out = real(arrivals, svc, n_groups)
        return out * 1.25 if n_groups == 2 else out

    monkeypatch.setattr(simulator, "_sojourn_recursion", broken)


@pytest.mark.parametrize("fault", ["altered", "unchanged"])
def test_plan_sweep_faults_are_not_correct(monkeypatch, fault):
    _break_plan_sweep(monkeypatch, fault)
    res = _run("qwen2-0.5b.chat")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["plan_mean_gap"]["value"] > 0.1


def _break_serving(monkeypatch, fault):
    import jax.numpy as jnp

    from repro.serving import ReplicatedServingEngine

    if fault == "altered":
        real = ReplicatedServingEngine._generate

        def altered(self, prompts):
            out = np.array(real(self, prompts))
            out[:, 3] = (out[:, 3] + 1) % self.cfg.vocab_size
            return out

        monkeypatch.setattr(ReplicatedServingEngine, "_generate", altered)
        return
    real_init = ReplicatedServingEngine.__init__

    def init(self, sc):
        real_init(self, sc)
        prefill, decode = self._prefill, self._decode
        if fault == "half":
            def half_prefill(p, batch):
                tokens = batch["tokens"]
                rows = jnp.arange(tokens.shape[0]) % max(tokens.shape[0] // 2,
                                                         1)
                return prefill(p, {"tokens": tokens[rows]})

            self._prefill = half_prefill
        elif fault == "unchanged":
            def stale_decode(p, state, tok, pos):
                logits, _ = decode(p, state, tok, pos)
                return logits, state

            self._decode = stale_decode

    monkeypatch.setattr(ReplicatedServingEngine, "__init__", init)


@pytest.mark.parametrize("fault", ["altered", "half", "unchanged"])
def test_serving_faults_are_not_correct(monkeypatch, fault):
    _break_serving(monkeypatch, fault)
    res = _run("qwen2-0.5b.chat")
    assert res["correct"] is False, res["checks"]
