"""Jit'd wrapper for the chunked WKV6 kernel.

Stability contract: the chunked form factors decay ratios as
exp(cumsum log w) products, so the per-chunk decay product must stay inside
fp32 range — with chunk=64 that holds for log w >= -0.25 per step
(w >= 0.78), far below RWKV6's trained decay floor.  Callers with ragged
sequence lengths fall back to the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.rwkv6_scan import ref
from repro.kernels.rwkv6_scan import rwkv6_scan as k


def wkv6(r, kk, v, w, u, state=None, *, chunk: int = k.DEFAULT_CHUNK):
    """r,k,v,w: (B,S,H,D); u: (H,D); optional initial state (B,H,D,D)."""
    b, s, h, d = r.shape
    if s % chunk:
        s0 = state if state is not None \
            else jnp.zeros((b, h, d, d), jnp.float32)
        return ref.wkv6(r, kk, v, w, u, s0)
    y, s_fin = k.wkv6_chunked(r, kk, v, w, u, chunk=chunk,
                              interpret=interpret_mode(r))
    if state is not None:
        # fold the incoming carry: the kernel ran with S_0 = 0, and the
        # recurrence is linear in the state, so add the decayed-carry terms.
        log_a = jnp.cumsum(jnp.log(w.astype(jnp.float32)), axis=1)
        a_prev = jnp.exp(log_a - jnp.log(w.astype(jnp.float32)))
        # y_t += (r_t ⊙ A_{t-1}) S_prev
        y = y + jnp.einsum("bshd,bhde->bshe",
                           r.astype(jnp.float32) * a_prev, state
                           ).astype(y.dtype)
        a_full = jnp.exp(log_a[:, -1])             # (B,H,D)
        s_fin = s_fin + a_full[..., None] * state
    return y, s_fin
