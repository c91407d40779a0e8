"""Every cell rehearsed on the CPU at a tiny size (the harness's look for
a chip skipped), the refusal without a TPU, and the cells' main programs
compiled for a described TPU v5e chip at their real sizes."""

import json
import os
import subprocess
import sys

import pytest

from harness import runner, spec

import tiny

BENCHMARK = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
CONFIGS = {c["name"]: c["file"] for c in BENCHMARK["configs"]}


@pytest.mark.parametrize("name", CELLS)
def test_refuses_without_a_tpu(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         name, "--seed", str(2**31 + 7), "--seconds", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_at_a_tiny_size(monkeypatch, name, traced):
    tiny.serve_this_model(monkeypatch, name)
    cell = spec.load_cell(name).replace(**tiny.overrides(name))
    res = runner.run_cell(cell, seed=2**31 + 99, seconds=1.0, traced=traced,
                          start=0.0, require_chip=False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in (cell.per_layer if traced else
                                cell.end_to_end)}
    # on the CPU no device trace exists, so device metrics stay silent
    assert set(res["metrics"]) <= want
    if not traced:
        assert set(res["metrics"]) == want
    else:
        assert res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


# what each configuration's file names, by key, and what each must offer
PER_CONFIG = {
    "counts": ("prefill_flops", "prefill_bytes", "decode_flops",
               "decode_bytes", "job_contexts"),
    "weights": ("make", "arch", "to_engine"),
    "reference": ("logits", "control_weights", "served_gaps"),
}


def test_every_metric_and_file_is_found_by_name():
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert hasattr(spec.load_module(f"metrics/{m['name']}.py"), "read")
    for c in BENCHMARK["configs"]:
        cfg = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for key, names in PER_CONFIG.items():
            assert key in cfg, f"{c['name']}: no {key!r} module"
            mod = spec.load_module(cfg[key])
            lacks = [n for n in names if not callable(getattr(mod, n, None))]
            assert not lacks, f"{c['name']}: {cfg[key]} lacks {lacks}"
        assert set(cfg.get("tiny", {})) >= {"config", "traffic"}, (
            f"{c['name']}: no 'tiny' block of CPU sizes")
    for name in CELLS:
        cell = spec.load_cell(name)
        spec.load_module(f"drivers/{cell.traffic['driver']}.py")
        for m in cell.end_to_end:
            assert m["name"] == "setup_s" or name in m["workloads"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_plan_sweep_compiles_for_v5e(one_chip, config):
    """The served engine's initial-plan sweep at its dispatch shapes: one
    cell per split of the replica groups, no straggler policy."""
    import jax.numpy as jnp

    from repro.kernels.sojourn_sweep import kernel

    with open(os.path.join(spec.ROOT, CONFIGS[config])) as f:
        dep = json.load(f)["deployment"]
    n, j = dep["n_server_groups"], dep["plan_trials"]
    f32, i32 = jnp.float32, jnp.int32
    for g in (b for b in range(1, n + 1) if n % b == 0):
        kernel.sojourn_cells_vmap.lower(
            _sds((j,), f32, one_chip), _sds((1, j, g), f32, one_chip),
            _sds((1, j, g), f32, one_chip), _sds((1,), i32, one_chip),
            _sds((1, 1), f32, one_chip), _sds((1, j), bool, one_chip),
            _sds((1,), i32, one_chip), resolve=False).compile()


@pytest.mark.parametrize("name", CELLS)
def test_serving_programs_compile_for_v5e(one_chip, name):
    """Full-width prefill and decode at the cell's shapes, and the
    reference forward pass at the size the check runs it."""
    import jax
    import jax.numpy as jnp

    from repro.models import Shard, decode_step, init_params, prefill
    from repro.models.lm import decode_state_shapes

    cell = spec.load_cell(name)
    cfg, tr = cell.config, cell.traffic
    ref = spec.load_module(cfg["reference"])
    weights = spec.load_module(cfg["weights"])
    arch = weights.arch(cfg)
    on_chip = lambda t: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), arch)))
    b, s, m = tr["batch_size"], tr["prompt_len"], tr["max_len"]
    shard = Shard.local()
    jax.jit(lambda p, x: prefill(arch, shard, p, x, max_len=m)).lower(
        params, {"tokens": _sds((b, s), jnp.int32, one_chip)}).compile()
    state = on_chip(decode_state_shapes(arch, b, m))
    jax.jit(lambda p, st, t, c: decode_step(arch, shard, p, st, t, c)).lower(
        params, state, _sds((b, 1), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    w = on_chip(jax.eval_shape(lambda: weights.make(0, cfg)))
    n, first = tr["check_block"], s - 1
    compiled = jax.jit(lambda w, t: ref.logits(w, t, cfg, first)).lower(
        w, _sds((n, s + tr["gen_tokens"] - 1), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8e9
