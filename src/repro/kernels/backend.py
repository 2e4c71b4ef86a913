"""Where the Pallas kernels run.

An accelerator always runs them compiled. The CPU backend has no Mosaic
lowering, so there they run through the Pallas interpreter — the mode the
tests use to check each kernel against its jnp oracle.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` when given; otherwise True exactly when the default
    backend is the CPU. Decided per call, at trace time."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
