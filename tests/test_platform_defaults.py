"""Platform-derived defaults: Pallas ``interpret``, the ``auto`` sweep
backend, and the compile-cache path.

Each default must follow the platform JAX actually runs on and must never
hide an accelerator: on a TPU the kernels compile (or fail loudly) instead
of running the interpreter, a backend that fails to initialise raises
instead of turning into the numpy path, and the compile cache lives at one
fixed path so a second run finds it.
"""

import inspect
import os
import pathlib

import jax
import pytest

from repro import compile_cache as CC
from repro.kernels import platform as KP
from repro.kernels.coded import kernel as coded_kernel
from repro.kernels.coded import ops as coded_ops
from repro.kernels.decode_attention import kernel as dec_kernel
from repro.kernels.decode_attention import ops as dec_ops
from repro.kernels.flash_attention import kernel as flash_kernel
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.held_experts import kernel as held_kernel
from repro.kernels.held_experts import ops as held_ops
from repro.kernels.sojourn_sweep import kernel as sweep_kernel
from repro.kernels.sojourn_sweep import ops as sweep_ops
from repro.kernels.ssm_scan import kernel as ssm_kernel
from repro.kernels.ssm_scan import ops as ssm_ops


def test_interpret_defaults_to_true_on_cpu():
    assert jax.default_backend() == "cpu"  # conftest pins JAX_PLATFORMS=cpu
    assert KP.resolve_interpret() is True
    assert KP.resolve_interpret(None) is True


def test_interpret_defaults_to_false_off_cpu(monkeypatch):
    monkeypatch.setattr(KP.jax, "default_backend", lambda: "tpu")
    assert KP.resolve_interpret() is False


@pytest.mark.parametrize("explicit", [True, False])
def test_explicit_interpret_passes_through(monkeypatch, explicit):
    assert KP.resolve_interpret(explicit) is explicit
    monkeypatch.setattr(KP.jax, "default_backend", lambda: "tpu")
    assert KP.resolve_interpret(explicit) is explicit


PALLAS_ENTRY_POINTS = [
    sweep_ops.sojourn_policy_cells,
    sweep_ops.coded_completion_cells,
    sweep_kernel.sojourn_cells_pallas,
    sweep_kernel.coded_cells_pallas,
    coded_ops.coded_combine,
    coded_ops.decode_combine,
    coded_ops.measure_coding_overhead,
    coded_kernel.combine_pallas,
    flash_ops.flash_attention,
    flash_kernel.flash_attention_kernel_call,
    dec_ops.decode_attention,
    dec_kernel.decode_attention_kernel_call,
    ssm_ops.ssd_scan,
    ssm_kernel.ssd_scan_kernel_call,
    held_ops.held_experts_gmm,
    held_kernel.held_experts_gmm_kernel_call,
]


@pytest.mark.parametrize(
    "fn", PALLAS_ENTRY_POINTS,
    ids=lambda f: f"{f.__module__.rsplit('.', 2)[-2]}.{f.__name__}")
def test_pallas_entry_points_take_interpret_from_the_platform(fn):
    """No entry point hard-codes interpret=True: the default is None, which
    resolve_interpret turns into the interpreter on CPU only."""
    assert inspect.signature(fn).parameters["interpret"].default is None


def test_auto_backend_raises_when_devices_fail(monkeypatch):
    """A backend that fails to initialise must surface, not silently turn
    'auto' into the numpy path."""

    def broken():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(sweep_ops.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        sweep_ops.resolve_backend("auto")


def test_auto_backend_on_cpu_is_numpy():
    assert sweep_ops.resolve_backend("auto") == "numpy"


def test_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(CC.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.compile_cache_dir() == str(tmp_path)
    assert CC.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing else is configured in code
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_repo_path(monkeypatch):
    monkeypatch.delenv(CC.CACHE_ENV, raising=False)
    repo_root = pathlib.Path(__file__).resolve().parents[1]
    expected = str(repo_root / ".jax_cache")
    assert CC.compile_cache_dir() == expected
    assert CC.compile_cache_dir() == expected  # no pid/time/temp component
    assert str(os.getpid()) not in expected


def test_use_compile_cache_sets_the_fixed_path(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv(CC.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = CC.use_compile_cache()
        assert path == CC.compile_cache_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
