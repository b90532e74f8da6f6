"""Pallas TPU kernels (validated via interpret=True on CPU) + jnp oracles."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode for a kernel call.

    ``None`` resolves to interpret mode exactly when the default backend
    is the CPU, the only backend that cannot compile the kernels.
    Interpret mode is refused on any other backend: a kernel on the chip
    always runs compiled."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"interpret=True on the {jax.default_backend()} backend; "
            f"interpret mode is for the CPU only")
    return bool(interpret)
