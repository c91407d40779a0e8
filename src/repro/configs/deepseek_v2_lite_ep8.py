"""DeepSeek-V2-Lite as one chip's share of an EP8 deployment.

Each MoE layer's 64 routed experts lie over 8 chips, 8 to a chip;
attention, the shared experts, the dense layer 0 and the vocabulary are
held whole on every chip.  This is rank 0: experts 0-7.  Its expert layer
routes over all 64 and passes on the part of the result that its own
experts give (with the shared experts), as it would before the exchange
between chips, which one chip does not run.  3,110,989,312 parameters.
"""

import dataclasses

from repro.configs.deepseek_v2_lite import CONFIG as _FULL

CONFIG = dataclasses.replace(
    _FULL,
    name="deepseek-v2-lite-ep8",
    moe=dataclasses.replace(_FULL.moe, n_held=8),
)
