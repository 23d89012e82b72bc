"""Pallas TPU kernel: Mamba-1 selective scan, channel-blocked.

TPU adaptation (DESIGN.md §2): the CUDA selective-scan kernel leans on
shared-memory staging and per-thread recurrences.  On TPU we block the
d_inner axis into (BD)-wide stripes held in VMEM and sweep the sequence in
chunks; the state h (BD, N) stays pinned in VMEM scratch across the
sequential chunk axis.  Mamba-1's full (Di, N) decay matrix precludes the
SSD matmul trick (that needs Mamba-2's scalar-per-head A), so the inner
C-step loop is VPU elementwise work over (BD, N) tiles + one (BD,N)x(N,)
contraction per step — still far better than HBM round-trips per token.

Grid: (B, Di/BD, S/C) with the chunk axis sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BD = 256
DEFAULT_CHUNK = 64


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                 h_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)            # (BD, N)

    def step(t, h):
        ut = u_ref[0, t, :].astype(jnp.float32)   # (BD,)
        dtt = dt_ref[0, t, :].astype(jnp.float32)
        bt = b_ref[0, t, :].astype(jnp.float32)   # (N,)
        ct = c_ref[0, t, :].astype(jnp.float32)
        da = jnp.exp(dtt[:, None] * a)            # (BD, N)
        h = da * h + (dtt * ut)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=-1)     # (BD,)
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == num_chunks - 1)
    def _emit():
        hout_ref[0] = h_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bd", "chunk", "interpret"))
def selective_scan_chunked(u, dt, a, b, c, *, bd: int = DEFAULT_BD,
                           chunk: int = DEFAULT_CHUNK,
                           interpret: bool = True):
    """u,dt: (B,S,Di); a: (Di,N); b,c: (B,S,N) -> (y (B,S,Di), h (B,Di,N)).

    Zero initial state; streaming carries are folded by ops.py.
    """
    bsz, s, di = u.shape
    n = a.shape[1]
    bd = min(bd, di)
    assert di % bd == 0 and s % chunk == 0, (di, bd, s, chunk)
    nc = s // chunk

    kernel = functools.partial(_scan_kernel, chunk=chunk, num_chunks=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(bsz, di // bd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((1, chunk, bd), lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((bd, n), lambda bi, di_, ci: (di_, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, di_, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, di_, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((1, bd, n), lambda bi, di_, ci: (bi, di_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), u.dtype),
            jax.ShapeDtypeStruct((bsz, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(u, dt, a, b, c)
    return y, h
