"""The Mamba selective scan as callers use it: ``scan`` runs the Pallas
kernel where ``uses_kernel`` says so and the reference ``lax.scan``
everywhere else; ``selective_scan`` is the kernel itself, with a ragged
S padded and the reference's VJP as its backward."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.mamba_scan import mamba_scan as k
from repro.kernels.mamba_scan import ref


def uses_kernel(platform: str, seq_len: int, d_inner: int) -> bool:
    """Whether a scan of ``seq_len`` steps over ``d_inner`` channels
    compiled for ``platform`` runs the kernel.  The one rule for both the
    dispatch (``scan``) and the ml island's path counters: a TPU, at
    least one chunk of steps (so decode at S = 1 keeps the reference),
    and whole 128-lane channel tiles."""
    return (platform == "tpu" and seq_len >= k.CHUNK
            and d_inner % k.LANES == 0)


def scan(u, dt, a, b, c, h0):
    """u,dt: (B,S,Di); a: (Di,N); b,c: (B,S,N); h0: (B,Di,N) fp32 ->
    (y (B,S,Di) f32, h (B,Di,N) f32), by the kernel or the reference."""
    platform = "cpu" if interpret_mode(u) else "tpu"
    if uses_kernel(platform, u.shape[1], u.shape[2]):
        return selective_scan(u, dt, a, b, c, h0)
    return ref.selective_scan(u, dt, a, b, c, h0)


def selective_scan(u, dt, a, b, c, h0, *, bd: int = k.BD,
                   chunk: int = k.CHUNK):
    """The kernel over any S: a ragged S is padded to whole chunks with
    dt = 0 (so exp(dt A) = 1 and no input enters: h and the kept rows
    are exact), and the padded rows are dropped."""
    # a channel block that tiles Di (whole 128-lane tiles where both are)
    return _scan(u, dt, a, b, c, h0, math.gcd(bd, u.shape[2]), chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(u, dt, a, b, c, h0, bd, chunk):
    s = u.shape[1]
    pad = -s % chunk
    if pad:
        u, dt, b, c = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                       for x in (u, dt, b, c))
    y, h = k.selective_scan_chunked(u, dt, a, b, c, h0, bd=bd, chunk=chunk,
                                    interpret=interpret_mode(u))
    return y[:, :s], h


def _scan_fwd(u, dt, a, b, c, h0, bd, chunk):
    return _scan(u, dt, a, b, c, h0, bd, chunk), (u, dt, a, b, c, h0)


def _scan_bwd(bd, chunk, residuals, cotangents):
    """The reference's VJP: ``jax.grad`` through the kernel works on a
    TPU (training), at the reference's cost."""
    _, vjp = jax.vjp(ref.selective_scan, *residuals)
    return vjp(cotangents)


_scan.defvjp(_scan_fwd, _scan_bwd)
