"""Jit'd public wrapper for flash attention — the ArrayIsland attention shim
(cfg.attn_impl == "flash").  Interpret mode on CPU; compiled on TPU."""
from __future__ import annotations

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_attention import flash_attention as k
from repro.kernels.flash_attention import ref


def flash_attention(q: jax.Array, kk: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = k.DEFAULT_BLOCK_Q,
                    block_k: int = k.DEFAULT_BLOCK_K) -> jax.Array:
    s, t = q.shape[1], kk.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, t)
    if s % bq or t % bk:
        # ragged tails fall back to the oracle (kernel wants aligned tiles)
        return ref.gqa_attention(q, kk, v, causal=causal)
    return k.flash_attention(q, kk, v, causal=causal, block_q=bq,
                             block_k=bk, interpret=interpret_mode(q))
