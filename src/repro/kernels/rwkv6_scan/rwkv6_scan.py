"""Pallas TPU kernel: chunked WKV6 linear recurrence.

TPU adaptation (DESIGN.md §2): the GPU reference (RWKV CUDA) walks the
sequence one token per thread-block iteration.  On TPU we use the chunked
linear-attention form so the inner loop is three (C x D) matmuls on the MXU
instead of S rank-1 VPU updates:

  with cumulative decays A_t = prod_{i<=t} w_i (per k-channel):
    inter   y_t += (r_t ⊙ A_{t-1}) S_0
    intra   y_t += sum_{j<t} ((r_t ⊙ A_{t-1}/A_j) · k_j) v_j   (masked matmul)
    bonus   y_t += (r_t · (u ⊙ k_t)) v_t                        (diagonal)
    state   S_C  = A_C ⊙ S_0 + (K ⊙ A_C/A)^T V

A_t/A_j <= 1 for j <= t (decays in (0,1)) so the ratios are stable.
Grid: (B*H, S/C) with the chunk axis sequential; S_0 carries in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref,
                state_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)            # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)            # decays in (0,1)
    u = u_ref[0].astype(jnp.float32)            # (1, D) bonus

    log_a = jnp.cumsum(jnp.log(w), axis=0)      # (C, D)
    a = jnp.exp(log_a)                          # A_t
    a_prev = jnp.exp(log_a - jnp.log(w))        # A_{t-1} = A_t / w_t

    s0 = state_ref[...]                         # (D, D)

    # inter-chunk: (r ⊙ A_{t-1}) @ S_0
    y = jnp.dot(r * a_prev, s0)

    # intra-chunk: masked ((r ⊙ A_{t-1}) @ (K / A)^T) @ V, strictly causal
    scores = jnp.dot(r * a_prev, (k / a).T)     # (C, C)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(cols < rows, scores, 0.0)
    y = y + jnp.dot(scores, v)

    # diagonal bonus: (r_t · (u ⊙ k_t)) v_t
    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)
    y = y + bonus * v

    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S_C = A_C ⊙ S_0 + (K ⊙ A_C/A)^T V
    a_c = a[-1:]                                # (1, D)
    state_ref[...] = a_c.T * s0 + jnp.dot((k * (a_c / a)).T, v)

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        sout_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_chunked(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                 u: jax.Array, *, chunk: int = DEFAULT_CHUNK,
                 interpret: bool = True):
    """r,k,v,w: (B,S,H,D) fp32; u: (H,D). Returns (y (B,S,H,D), S (B,H,D,D)).

    Zero initial state (sequence mode); streaming callers fold their carry
    via the ops.py wrapper.
    """
    b, s, h, d = r.shape
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    def to_bh(x):
        return x.swapaxes(1, 2).reshape(b * h, s, d)

    rb, kb, vb, wb = map(to_bh, (r, k, v, w))
    ub = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, num_chunks=nc)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, d), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, d, d), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(rb, kb, vb, wb, ub)

    y = y.reshape(b, h, s, d).swapaxes(1, 2)
    return y, s_out.reshape(b, h, d, d)
