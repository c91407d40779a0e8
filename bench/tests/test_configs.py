"""Every per-configuration fact comes from the configuration's own file.

A second configuration's cell is new files plus a ``configs`` and a
``workloads`` entry: the harness, the driver, the metric readers and the
per-cell tests name no configuration's files or widths, a twin of the
qwen2 configuration runs through the whole harness without an edit, the
engine's shapes are held to the configuration field by field, and the
readers that count operations and bytes read the counts module the
configuration names.
"""

import ast
import copy
import glob
import json
import os
import types

import pytest

from harness import runner, spec, tracing

import tiny

BENCHMARK = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
CONFIGS = [json.load(open(os.path.join(spec.ROOT, c["file"])))
           for c in BENCHMARK["configs"]]
QWEN = "configs/qwen2-0.5b.json"

# the generic parts, which must serve any configuration unchanged
GENERIC = ([p for d in ("harness", "drivers", "metrics")
            for p in glob.glob(os.path.join(spec.BENCH, d, "**", "*"),
                               recursive=True)
            if os.path.isfile(p) and "__pycache__" not in p]
           + [os.path.join(spec.BENCH, "tests", f)
              for f in ("tiny.py", "test_cells.py")])


def _names(cfg: dict) -> set:
    """Words that identify a configuration: its name, its model type, and
    the files it names for its reference, weights and counts."""
    out = {cfg["name"], cfg["model_type"]}
    for key in ("reference", "weights", "counts"):
        out.add(os.path.splitext(os.path.basename(cfg[key]))[0])
    return out


def _widths(cfg: dict) -> set:
    """Every top-level whole number of the file but its depth, and its
    head size; counts of 8 or less are ordinary constants too and left
    out."""
    out = {v for k, v in cfg.items() if type(v) is int
           and k != "num_hidden_layers"}
    out.add(cfg["hidden_size"] // cfg["num_attention_heads"])
    return {v for v in out if v > 8}


def test_generic_files_name_no_configuration():
    words = set().union(*map(_names, CONFIGS)) | {"qwen2", "dense_decoder"}
    widths = set().union(*map(_widths, CONFIGS))
    assert len(GENERIC) > 10
    found = []
    for path in GENERIC:
        with open(path, errors="replace") as f:
            text = f.read()
        rel = os.path.relpath(path, spec.BENCH)
        found += [(rel, w) for w in sorted(words) if w.lower() in
                  text.lower()]
        if path.endswith(".py"):
            found += [(rel, node.value) for node in ast.walk(ast.parse(text))
                      if isinstance(node, ast.Constant)
                      and type(node.value) is int and node.value in widths]
    assert not found, f"configuration names or widths in generic files: {found}"


def _snapshot() -> dict:
    out = {}
    for path in glob.glob(os.path.join(spec.BENCH, "**", "*"),
                          recursive=True):
        if os.path.isfile(path) and "__pycache__" not in path:
            st = os.stat(path)
            out[path] = (st.st_size, st.st_mtime_ns)
    return out


def _twin() -> spec.Cell:
    """The qwen2 configuration under another name at 2 layers, with a tiny
    block and a counts key of its own, served under the chat traffic."""
    cfg = copy.deepcopy(spec.load_json(QWEN))
    cfg.update(name="twin-2l", num_hidden_layers=2,
               tiny={"config": {"num_hidden_layers": 2, "vocab_size": 16384},
                     "traffic": dict(cfg["tiny"]["traffic"], gen_tokens=6)})
    return spec.Cell(name="twin-2l.chat", chips=1, config=cfg,
                     traffic=spec.load_json("traffic/chat.json"),
                     end_to_end=BENCHMARK["end_to_end"],
                     per_layer=BENCHMARK["per_layer"])


@pytest.mark.parametrize("traced", [False, True])
def test_a_second_configuration_needs_no_edit(monkeypatch, traced):
    cell = _twin()
    before = _snapshot()
    # the engine serves the twin's 2 layers: setup refuses any other model
    tiny.serve_this_model(monkeypatch, cell)
    small = cell.replace(**tiny.overrides(cell))
    assert small.config["num_hidden_layers"] == 2
    res = runner.run_cell(small, seed=2**31 + 41, seconds=1.0, traced=traced,
                          start=0.0, require_chip=False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    entries = cell.per_layer if traced else cell.end_to_end
    units = {m["name"]: m["unit"] for m in entries}
    assert {n: m["unit"] for n, m in res["metrics"].items()} == {
        n: units[n] for n in res["metrics"]}
    if not traced:
        assert set(res["metrics"]) == set(units)
    else:
        # no device plane on the CPU: the trace-based readers stay silent,
        # so read the twin's counts through a stub trace of the chat shape
        got = _read_counted(_stub_run(cell.config))
        want = _read_counted(_stub_run(spec.load_json(QWEN)))
        assert all(got[n] < want[n] for n in COUNTED)
    assert _snapshot() == before


def test_tiny_needs_a_tiny_block():
    cell = _twin()
    del cell.config["tiny"]
    with pytest.raises(KeyError, match="no 'tiny' block"):
        tiny.overrides(cell)


class _Engine:
    def __init__(self, cfg):
        self.cfg = cfg


@pytest.mark.parametrize("field", ["n_kv_heads", "d_ff"])
def test_shape_check_refuses_a_misshaped_engine(field):
    import dataclasses

    serve = spec.load_module("drivers/serve.py")
    cfg = spec.load_json(QWEN)
    want = spec.load_module(cfg["weights"]).arch(cfg)
    serve._assert_shapes(_Engine(want), want)
    off = dataclasses.replace(want, **{field: getattr(want, field) + 1})
    with pytest.raises(ValueError, match=f"engine serves {field}=") as e:
        serve._assert_shapes(_Engine(off), want)
    assert f"configuration states {field}=" in str(e.value)


@pytest.mark.parametrize("key", ["hidden_size", "num_key_value_heads",
                                 "intermediate_size"])
def test_arch_refuses_a_width_the_program_lacks(key):
    cfg = spec.load_json(QWEN)
    weights = spec.load_module(cfg["weights"])
    arch = weights.arch(cfg)
    assert (arch.n_layers, arch.vocab_size) == (cfg["num_hidden_layers"],
                                                cfg["vocab_size"])
    with pytest.raises(ValueError, match=f"configuration states {key}="):
        weights.arch(dict(cfg, **{key: cfg[key] + 1}))


COUNTED = ("prefill_roofline", "decode_roofline", "serve_mfu")


def _stub_run(cfg):
    """A traced run of two chat jobs: per job a prompt draw, a prefill of
    71.1 ms, 127 decode steps of 3.739 ms and a join, in a 1.2 s window
    on a TPU v5e."""
    mods, t = [], 0.0
    for _ in range(2):
        for name, n, dur in (("jit_serve_prompts(11)", 1, 0.0003),
                             ("jit_serve_prefill(22)", 1, 0.07110),
                             ("jit_serve_decode(33)", 127, 0.003739),
                             ("jit_serve_join(44)", 1, 0.00002)):
            for _ in range(n):
                mods.append((t, t + dur, name.split("(")[0], name))
                t += dur + 1e-5
    trace = tracing.Trace(modules=mods, op_self={},
                          spans={"bench.window": [(0.0, 1.2)]}, n_devices=1)
    facts = {"prefill_calls": 2, "decode_calls": 254, "batch": 8,
             "prompt_len": 1024, "gen_tokens": 128, "model": cfg}
    peaks = spec.load_json("harness/peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(trace=trace, facts=facts, peaks=peaks)


def _read_counted(run) -> dict:
    return {n: spec.load_module(f"metrics/{n}.py").read(run)
            for n in COUNTED}


def test_counted_readers_read_the_configurations_counts():
    """The values the readers gave when they loaded the dense decoder's
    counts by a literal path, on the same stub run."""
    got = _read_counted(_stub_run(spec.load_json(QWEN)))
    assert got == {"prefill_roofline": 44.44971397928133,
                   "decode_roofline": 35.75878867796481,
                   "serve_mfu": 6.19691205869374}
