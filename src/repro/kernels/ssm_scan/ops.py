"""jit'd public wrapper for the chunked SSD scan kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.ssm_scan.kernel import ssd_scan_kernel_call
from repro.kernels.ssm_scan.ref import ssd_scan_ref

__all__ = ["ssd_scan"]


@functools.partial(jax.jit, static_argnames=("chunk", "impl", "interpret"))
def ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk: int = 128,
             impl: str = "pallas", interpret: Optional[bool] = None):
    """Chunked SSD scan.  x (B,S,H,P); dt (B,S,H); a_log (H,);
    b, c (B,S,G,N); d_skip (H,).  Returns (y, final_state)."""
    if impl == "xla":
        from repro.models.ssm import ssd_chunked

        s = x.shape[1]
        eff = min(chunk, s) if s % chunk else chunk
        if s % eff:
            eff = s
        return ssd_chunked(x, dt, a_log, b, c, d_skip, eff)
    if impl == "ref":
        return ssd_scan_ref(x, dt, a_log, b, c, d_skip)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    s = x.shape[1]
    eff = chunk if s % chunk == 0 else s
    return ssd_scan_kernel_call(
        x, dt, a_log, b, c, d_skip, chunk=eff, interpret=interpret
    )
