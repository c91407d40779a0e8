"""Encode/decode kernels for coded computation (PR 9).

Coding is only a win if its overhead is MEASURED, not assumed free: a
coded plan pays an encode (coefficient-combine of the data blocks before
dispatch) and a decode (weight-combine of the first k responses) that
replication never pays.  This package supplies that combine as one kernel
body on the repo's three backend lanes — numpy reference, jit JAX, Pallas
(compiled on an accelerator, interpreted on CPU) — plus
:func:`~.ops.measure_coding_overhead`, the wall-clock probe the planner
uses to resolve ``CodingCandidate(encode_overhead=None)`` before scoring
the candidate.
"""

from .ops import (
    BACKENDS,
    coded_combine,
    decode_combine,
    encode_matrix,
    measure_coding_overhead,
)

__all__ = [
    "BACKENDS",
    "coded_combine",
    "decode_combine",
    "encode_matrix",
    "measure_coding_overhead",
]
