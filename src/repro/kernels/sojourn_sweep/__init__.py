"""Accelerator-resident sojourn/policy sweep kernels.

The planning sweep's inner loop — the FIFO multi-server sojourn recursion
under a straggler policy (none / clone / relaunch / hedged) — re-expressed
as a fixed-shape scan so every (dist, B, policy) cell of a sweep runs on an
accelerator from one shared-CRN draw matrix:

* :mod:`.ref`    — numpy reference of the scan formulation (the oracle the
  event-driven simulator recursions are pinned against, bit-for-bit at f64);
* :mod:`.kernel` — the shared jnp cell recursion, its ``lax.scan`` + vmap
  backend, and the Pallas kernel (compiled on TPU; interpreted on a CPU
  backend, which is how the tests run it);
* :mod:`.ops`    — the batched entry point :func:`~.ops.sojourn_policy_cells`
  with backend dispatch (``numpy`` / ``jax`` / ``pallas``) and
  ``shard_map`` sharding of the cell axis across a device mesh.
"""

from .ops import (
    KIND_CLONE,
    KIND_HEDGED,
    KIND_NONE,
    KIND_RELAUNCH,
    cells_mesh,
    coded_completion_cells,
    hedge_mask,
    policy_kind_code,
    resolve_backend,
    sojourn_policy_cells,
)

__all__ = [
    "KIND_NONE",
    "KIND_CLONE",
    "KIND_RELAUNCH",
    "KIND_HEDGED",
    "cells_mesh",
    "coded_completion_cells",
    "hedge_mask",
    "policy_kind_code",
    "resolve_backend",
    "sojourn_policy_cells",
]
