"""Platform-derived defaults shared by every Pallas entry point."""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve a Pallas ``interpret`` knob: ``None`` means "interpret on a
    CPU backend only", so an accelerator always runs (or refuses to
    compile) the real kernel instead of silently running the interpreter.
    An explicit bool passes through."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
