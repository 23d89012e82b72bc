"""Pure-jnp reference for the Mamba-1 selective scan (per-step lax.scan):
the model's scan wherever ``ops.uses_kernel`` says no (the CPU, decode at
S = 1), the kernel's backward, and the tests' oracle."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def selective_scan(u: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, h0: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """u,dt: (B,S,Di); a: (Di,N); b,c: (B,S,N); h0: (B,Di,N) fp32.

      h_t = exp(dt_t ⊙ A) h_{t-1} + (dt_t u_t) ⊗ B_t;  y_t = h_t · C_t
    """
    def step(h, inp):
        ut, dtt, bt, ct = inp
        da = jnp.exp(dtt[..., None] * a[None])
        h = da * h + (dtt * ut)[..., None] * bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    xs = (u.swapaxes(0, 1).astype(jnp.float32),
          dt.swapaxes(0, 1).astype(jnp.float32),
          b.swapaxes(0, 1).astype(jnp.float32),
          c.swapaxes(0, 1).astype(jnp.float32))
    h_final, ys = jax.lax.scan(step, h0, xs)
    return ys.swapaxes(0, 1), h_final
