"""``chip_smoke.py`` on the CPU: its refusal without a TPU, and its phases
at a tiny size.

On a TPU the script runs the phases at full size; here each phase runs its
own checks (bitwise backend parity, the numpy reference, the direct greedy
loop, the float32 logits) on a grid small enough for interpret-mode Pallas,
so a broken check is caught before it costs a chip run.
"""

import importlib.util
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY_GRID = dict(n_workers=120, splits=(2, 4, 6), n_jobs=40, n_atoms=500)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO_ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def clock(smoke):
    return smoke.CompileClock()


def test_refuses_without_a_tpu(smoke, monkeypatch, tmp_path, capsys):
    # with the variable set the script configures no cache in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU visible" in out.err


def test_fails_alone_without_the_repo(tmp_path):
    shutil.copy(REPO_ROOT / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_planning_phase_at_tiny_size(smoke, clock, capsys):
    smoke.phase_planning(clock, 0, k=3, **TINY_GRID)
    out = capsys.readouterr().out
    assert "pallas == jax bitwise" in out
    assert "Plan.backend=pallas" in out


def test_serving_phase_at_tiny_size(smoke, clock, capsys):
    smoke.phase_serving(clock, 0, reduced=True, n_requests=8, batch=4,
                        prompt_len=16, gen_tokens=4, max_len=32)
    out = capsys.readouterr().out
    assert "all 8 requests answered with 4 tokens" in out


def test_sharded_phase_at_tiny_size(smoke, clock, capsys):
    smoke.phase_sharded(clock, 0, jax.devices()[:1], ks=(3, 2), **TINY_GRID)
    out = capsys.readouterr().out
    assert out.count("cells mesh == one chip bitwise") == 2
