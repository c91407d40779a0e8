"""Batched sojourn/policy cell evaluation with backend + mesh dispatch.

:func:`sojourn_policy_cells` is the seam the simulator sweeps call: it
takes the fully materialized per-cell service tensors (built host-side
from the shared-CRN draw matrix) and evaluates every (cell, policy) pair
on the requested backend —

* ``"numpy"``  — the plain-Python reference (:mod:`.ref`), used for
  parity pins and as the no-device fallback;
* ``"jax"``    — jit + vmap over cells×policies, optionally ``shard_map``
  sharded over the cell axis of a device mesh (the fleet-scale path:
  ``EmpiricalPlanner``'s bootstrap resamples ride the cell axis, so
  K=256 resamples spread across devices in one dispatch);
* ``"pallas"`` — the Pallas kernel over a (cells, policies) grid,
  compiled for the accelerator; in interpret mode only on a CPU backend
  (see :func:`repro.kernels.platform.resolve_interpret`).

Sharding happens only when the caller passes a ``mesh``: the cell axis
is then padded to a multiple of the mesh size (dummy cells run
``n_groups=1`` on zero service draws) and sliced back afterwards.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from . import kernel as _kernel
from . import ref as _ref
from .ref import KIND_CLONE, KIND_HEDGED, KIND_NONE, KIND_RELAUNCH

BACKENDS = ("numpy", "jax", "pallas")

_KIND_CODES = {
    "none": KIND_NONE,
    "clone": KIND_CLONE,
    "relaunch": KIND_RELAUNCH,
    "hedged": KIND_HEDGED,
}


def policy_kind_code(kind: str) -> int:
    """Integer kernel code for a `PolicyCandidate.kind` string."""
    try:
        return _KIND_CODES[kind]
    except KeyError:
        raise ValueError(f"unknown policy kind {kind!r} "
                         f"(expected one of {sorted(_KIND_CODES)})") from None


def hedge_mask(n_jobs: int, fraction: float) -> np.ndarray:
    """Deterministic-stride hedge mask: job i hedges iff
    ``floor((i+1)f) > floor(if)``, evaluated in f64 on the host so every
    backend sees the identical pattern regardless of device precision."""
    i = np.arange(n_jobs, dtype=np.float64)
    f = float(fraction)
    return np.floor((i + 1.0) * f) > np.floor(i * f)


def resolve_backend(backend: str) -> str:
    """Resolve the ``"auto"`` sweep backend: an accelerator device picks
    ``"jax"`` (the compiled vmap/shard_map path); CPU-only keeps the
    bit-stable ``"numpy"`` event-driven path.  A backend that fails to
    initialise raises: it never turns into ``"numpy"``."""
    if backend == "auto":
        devices = jax.devices()
        return "jax" if any(d.platform != "cpu" for d in devices) else "numpy"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r} (expected 'auto' or one of {BACKENDS})")
    return backend


def coded_completion_cells(times, ks, *, backend: str = "jax",
                           interpret: Optional[bool] = None):
    """k-of-N completion for a batch of coded cells on one backend.

    The coded twin of :func:`sojourn_policy_cells`: ``times`` (C, T, N)
    holds the per-cell load-scaled worker draws (built host-side from the
    shared CRN matrix), ``ks`` (C,) the completion quorums, and the
    result (C, T) is the k-th order statistic per trial.  Selection is
    value-exact, so numpy/jax/pallas agree bit-for-bit at equal dtype —
    the parity pin that lets coded sweep cells ride the same ``backend=``
    lanes as the replication cells.
    """
    if backend == "numpy":
        return _ref.coded_completion_reference(times, ks)
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    fdtype = jnp.result_type(float)
    times = jnp.asarray(times, fdtype)
    ks = jnp.asarray(ks, jnp.int32)
    if backend == "pallas":
        return _kernel.coded_cells_pallas(times, ks, interpret=interpret)
    return _kernel.coded_cells_vmap(times, ks)


def cells_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D ``cells`` mesh over the given (default: all) devices."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), ("cells",))


@functools.lru_cache(maxsize=8)
def _sharded_cells_fn(mesh: Mesh, resolve: bool = True):
    spec_c = PartitionSpec("cells")
    spec_c3 = PartitionSpec("cells", None, None)
    spec_c2 = PartitionSpec("cells", None)
    rep = PartitionSpec()
    fn = jax.shard_map(
        functools.partial(_kernel._cells_fn, resolve=resolve),
        mesh=mesh,
        in_specs=(rep, spec_c3, spec_c3, rep, spec_c2, rep, spec_c),
        out_specs=(spec_c3, spec_c2),
        check_vma=False,
    )
    return jax.jit(fn)


def sojourn_policy_cells(arrivals, svc, alt, kinds, thresholds, hedge_masks,
                         n_groups, *, backend: str = "jax",
                         mesh: Optional[Mesh] = None,
                         interpret: Optional[bool] = None):
    """Evaluate all (cell, policy) sojourn recursions on one backend.

    Parameters
    ----------
    arrivals : (J,) arrival times shared by every cell.
    svc, alt : (C, J, G) primary / redundant service draws per cell,
        group-minimized and load-scaled; padded columns beyond
        ``n_groups[c]`` are never read.
    kinds : (P,) int policy codes (see :func:`policy_kind_code`).
    thresholds : (C, P) trigger delays (``inf`` disables arming).
    hedge_masks : (P, J) bool stride masks (see :func:`hedge_mask`).
    n_groups : (C,) live group count per cell.
    backend : ``"numpy"`` | ``"jax"`` | ``"pallas"`` (resolve ``"auto"``
        with :func:`resolve_backend` first).
    mesh : optional device mesh; the cell axis is sharded over it
        (``"jax"`` backend only — the Pallas grid is device-local).
        Without one the dispatch runs on the default device, however
        many devices are visible.
    interpret : run the Pallas kernel in interpreter mode (default: only
        on a CPU backend).

    Returns
    -------
    (sojourns, extras) : ``(C, P, J)`` float and ``(C, P)`` int arrays
        (numpy for the numpy backend, device arrays otherwise).
    """
    if backend == "numpy":
        return _ref.sojourn_cells_reference(arrivals, svc, alt, kinds,
                                            thresholds, hedge_masks, n_groups)
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")

    # Static specialization: when no lane can arm a trigger (no
    # clone/relaunch policy with a finite threshold), the kernels skip the
    # event-resolution pass entirely — bit-identical (the pass is an
    # identity for unarmed lanes) and about 2x cheaper per dispatch, which
    # is what the grouped per-policy-family dispatch in the simulator
    # sweeps exists to exploit.
    kinds_np = np.asarray(kinds)
    trigger = (kinds_np == KIND_CLONE) | (kinds_np == KIND_RELAUNCH)
    resolve = bool(np.any(trigger[None, :]
                          & np.isfinite(np.asarray(thresholds))))

    fdtype = jnp.result_type(float)
    arrivals = jnp.asarray(arrivals, fdtype)
    svc = jnp.asarray(svc, fdtype)
    alt = jnp.asarray(alt, fdtype)
    kinds = jnp.asarray(kinds, jnp.int32)
    thresholds = jnp.asarray(thresholds, fdtype)
    hedge_masks = jnp.asarray(hedge_masks, bool)
    n_groups = jnp.asarray(n_groups, jnp.int32)

    if backend == "pallas":
        return _kernel.sojourn_cells_pallas(arrivals, svc, alt, kinds,
                                            thresholds, hedge_masks, n_groups,
                                            interpret=interpret,
                                            resolve=resolve)

    if mesh is None:
        return _kernel.sojourn_cells_vmap(arrivals, svc, alt, kinds,
                                          thresholds, hedge_masks, n_groups,
                                          resolve=resolve)

    n_cells = svc.shape[0]
    n_dev = mesh.devices.size
    pad = (-n_cells) % n_dev
    if pad:
        svc = jnp.pad(svc, ((0, pad), (0, 0), (0, 0)))
        alt = jnp.pad(alt, ((0, pad), (0, 0), (0, 0)))
        thresholds = jnp.pad(thresholds, ((0, pad), (0, 0)),
                             constant_values=jnp.inf)
        n_groups = jnp.pad(n_groups, (0, pad), constant_values=1)
    out, extra = _sharded_cells_fn(mesh, resolve)(arrivals, svc, alt, kinds,
                                                  thresholds, hedge_masks,
                                                  n_groups)
    if pad:
        out = out[:n_cells]
        extra = extra[:n_cells]
    return out, extra
