"""Jit'd wrapper for the chunked Mamba selective scan; folds streaming
state carries (the recurrence is linear in h0) and falls back to the
oracle on ragged shapes."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.mamba_scan import mamba_scan as k
from repro.kernels.mamba_scan import ref


def selective_scan(u, dt, a, b, c, h0=None, *, bd: int = k.DEFAULT_BD,
                   chunk: int = k.DEFAULT_CHUNK):
    bsz, s, di = u.shape
    n = a.shape[1]
    bd = min(bd, di)
    if s % chunk or di % bd:
        h_init = h0 if h0 is not None \
            else jnp.zeros((bsz, di, n), jnp.float32)
        return ref.selective_scan(u, dt, a, b, c, h_init)
    y, h = k.selective_scan_chunked(u, dt, a, b, c, bd=bd, chunk=chunk,
                                    interpret=interpret_mode(u))
    if h0 is not None:
        # linear-in-state: add decayed-h0 contributions
        dtf = dt.astype(jnp.float32)
        log_da = dtf[..., None] * a[None, None]          # (B,S,Di,N)
        cum = jnp.cumsum(log_da, axis=1)
        decay = jnp.exp(cum)                              # prod_{i<=t} da_i
        y = y + jnp.einsum("bsdn,bdn,bsn->bsd", decay, h0,
                           c.astype(jnp.float32)).astype(y.dtype)
        h = h + jnp.exp(cum[:, -1]) * h0
    return y, h
