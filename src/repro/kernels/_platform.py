"""Where the kernels run: the one place that asks whether JAX is on the CPU.

On the CPU a Pallas kernel can only run in interpret mode, and the device
plane serves from the jnp oracle instead; on an accelerator the compiled
kernel is the default.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["on_cpu", "resolve_interpret"]


def on_cpu() -> bool:
    """True when JAX's default backend is the CPU."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret mode exactly when JAX's backend is the CPU."""
    if interpret is None:
        return on_cpu()
    return bool(interpret)
