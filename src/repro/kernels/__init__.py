"""Pallas TPU kernels for the paper's compute hot spots (ops.py wraps them,
ref.py holds the pure-jnp oracles)."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The ONE decision between compiled and interpreted Pallas.

    An explicit bool wins (tests compile for a described TPU from a CPU
    process with `interpret=False`); `None` interprets only on the CPU
    backend, so every TPU caller compiles the real kernel."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"
