"""Pallas kernels: ``<name>.py`` (the kernel) + ``ops.py`` (the jitted
wrapper callers use) + ``ref.py`` (the plain-jnp reference) per kernel."""
from __future__ import annotations

import jax


def interpret_mode(x) -> bool:
    """Whether a Pallas call over ``x`` must run interpreted: False
    exactly when it lives on a TPU (Mosaic lowers only there).  Decided
    per call, never at import.  A traced value carries no placement, so
    it takes the backend the enclosing jit compiles for by default."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return any(d.platform != "tpu" for d in x.devices())
    return jax.default_backend() != "tpu"
