"""TPU-target Pallas kernels for the compute hot-spots of the assigned
architectures (the paper itself has no kernel-level contribution — these
serve the LM substrate; see DESIGN.md §3 'Kernel policy').

Each kernel ships as kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper) and ref.py (pure-jnp oracle), validated in interpret mode on CPU
(``interpret`` defaults to the platform: compiled on TPU).
"""

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssm_scan.ops import ssd_scan

__all__ = ["decode_attention", "flash_attention", "ssd_scan"]
