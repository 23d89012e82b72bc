"""Pallas TPU flash attention (causal, GQA-aware).

TPU adaptation notes (DESIGN.md §2): blockwise online-softmax with
(BLOCK_Q x Dh) query tiles resident in VMEM and a sequential sweep over
(BLOCK_K x Dh) key/value tiles; the two matmuls per tile land on the MXU
with 128-aligned contraction dims.  The m/l/acc carries live in VMEM
scratch across the innermost (arbitrary-semantics) grid dimension —
the canonical TPU flash pattern, not a CUDA-warp port.

Causally-skipped tiles are genuinely skipped via pl.when, so the FLOPs
match the ~S^2/2 causal roofline rather than S^2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, num_kb: int,
                  causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Tiles strictly above the diagonal contribute nothing under causality.
    needed = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)      # (BQ, Dh)
        k = k_ref[0, :, 0, :].astype(jnp.float32)      # (BK, Dh)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jnp.dot(q, k.T) * scale                     # (BQ, BK) on MXU
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # (BQ, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == num_kb - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, 0, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = True) -> jax.Array:
    """q: (B,S,Hq,Dh); k,v: (B,T,Hkv,Dh). Returns (B,S,Hq,Dh)."""
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    num_qb = s // block_q
    num_kb = t // block_k

    kernel = functools.partial(
        _flash_kernel, scale=dh ** -0.5, block_q=block_q, block_k=block_k,
        num_kb=num_kb, causal=causal)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, dh),
                         lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, block_k, 1, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g, 0)),
            pl.BlockSpec((1, block_k, 1, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, dh),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, hq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
