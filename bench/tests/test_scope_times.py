"""``checks/scope_times.py`` on the CPU: the served programs' ops mapped to
the model's named scopes, at the tiny size of the DeepSeek-V2-Lite cell,
and device time summed by scope on a stub trace."""

from types import SimpleNamespace

from harness import runner, spec

import tiny

CELL = "deepseek-v2-lite.chat"


def test_every_scope_has_ops_in_both_programs(monkeypatch):
    """Through the driver's own set-up, which wraps the programs to count
    their calls."""
    tiny.serve_this_model(monkeypatch, CELL)
    cell = spec.load_cell(CELL).replace(**tiny.overrides(CELL))
    tool = spec.load_module("checks/scope_times.py")
    driver = spec.load_module(f"drivers/{cell.traffic['driver']}.py")
    st = driver.setup(runner.Run(cell=cell, seed=2**31 + 5, seconds=0.0,
                                 traced=False))
    scopes = tool.program_scopes(st)
    assert sorted(scopes) == ["jit_serve_decode", "jit_serve_prefill"]
    for name, ops in scopes.items():
        assert set(ops.values()) == set(tool.SCOPES) | {"other"}, name


def test_device_time_is_summed_by_scope_per_execution():
    tool = spec.load_module("checks/scope_times.py")
    trace = SimpleNamespace(
        modules=[(0.0, 1.0, "jit_serve_decode", 0)] * 2
        + [(0.0, 1.0, "jit_serve_prefill", 0)],
        op_self={"jit_serve_decode/dot.1": 0.004,
                 "jit_serve_decode/sort.2": 0.002,
                 "jit_serve_decode/copy.3": 0.001,
                 "jit_serve_prefill/dot.1": 1.0})
    got = tool.by_scope(trace, "jit_serve_decode",
                        {"dot.1": "moe.experts", "sort.2": "moe.experts"})
    assert got["executions"] == 2
    assert got["ms_per_execution"] == {"moe.experts": 3.0, "other": 0.5}
    assert got["top_ops_ms"]["moe.experts"] == [(2.0, "dot.1"),
                                                (1.0, "sort.2")]
