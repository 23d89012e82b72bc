"""GQA attention: reference einsum implementation (used for lowering/dry-run
and CPU smoke tests) plus the dispatch point for the Pallas flash kernel shim.

The reference path is deliberately written so XLA SPMD can shard it either by
heads (``kv_heads -> model``) or by cache sequence (``kv_seq -> model``); in
the latter case the softmax max/sum reductions over the sharded axis lower to
the expected all-reduces (see DESIGN.md §5).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig
from repro.sharding import logical as L
from repro.sharding.logical import ParamSpec

NEG_INF = -1e30


def attn_specs(cfg: ModelConfig, *, cross: bool = False) -> dict:
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((d, hq, dh), (L.EMBED, L.HEADS, L.HEAD_DIM)),
        "wk": ParamSpec((d, hkv, dh), (L.EMBED, L.KV_HEADS, L.HEAD_DIM)),
        "wv": ParamSpec((d, hkv, dh), (L.EMBED, L.KV_HEADS, L.HEAD_DIM)),
        "wo": ParamSpec((hq, dh, d), (L.HEADS, L.HEAD_DIM, L.EMBED)),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((hq, dh), (L.HEADS, L.HEAD_DIM), init="zeros")
        specs["bk"] = ParamSpec((hkv, dh), (L.KV_HEADS, L.HEAD_DIM),
                                init="zeros")
        specs["bv"] = ParamSpec((hkv, dh), (L.KV_HEADS, L.HEAD_DIM),
                                init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((dh,), (L.HEAD_DIM,), init="ones")
        specs["k_norm"] = ParamSpec((dh,), (L.HEAD_DIM,), init="ones")
    return specs


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def project_qkv(params: dict, x: jax.Array, cfg: ModelConfig, rules,
                positions: Optional[jax.Array]
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if cfg.qk_norm:
        q = _rms(q, params["q_norm"], cfg.norm_eps)
        k = _rms(k, params["k_norm"], cfg.norm_eps)
    if cfg.uses_rope and positions is not None:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    q = L.constrain(q, rules, (L.BATCH, L.SEQ, L.HEADS, L.HEAD_DIM))
    k = L.constrain(k, rules, (L.BATCH, L.SEQ, L.KV_HEADS, L.HEAD_DIM))
    v = L.constrain(v, rules, (L.BATCH, L.SEQ, L.KV_HEADS, L.HEAD_DIM))
    return q, k, v


def gqa_attend(q: jax.Array, k: jax.Array, v: jax.Array,
               mask: Optional[jax.Array], cfg: ModelConfig, rules,
               ctx_sharded: bool = False) -> jax.Array:
    """q: (B,S,Hq,Dh); k,v: (B,T,Hkv,Dh); mask broadcastable to (B,1,1,S,T).

    ``ctx_sharded`` pins the score/probability matrices KV_SEQ-sharded
    (context parallelism): SPMD propagation alone prefers all-gathering k
    and replicating the S×T scores (verified in §Perf A1), so the
    constraint must sit on the scores themselves; XLA then inserts the
    softmax max/sum all-reduces and the pv partial-sum psum.
    """
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, s, hkv, groups, dh)
    scale = dh ** -0.5
    scores = jnp.einsum("bsngd,btnd->bngst", qg, k) * scale
    scores = scores.astype(jnp.float32)
    score_axes = (L.BATCH, L.KV_HEADS, None, L.SEQ, L.KV_SEQ)
    if ctx_sharded:
        scores = L.constrain(scores, rules, score_axes)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if ctx_sharded:
        probs = L.constrain(probs, rules, score_axes)
    out = jnp.einsum("bngst,btnd->bsngd", probs, v)
    out = out.reshape(b, s, hq, dh)
    return L.constrain(out, rules, (L.BATCH, L.SEQ, L.HEADS, L.HEAD_DIM))


def causal_mask(s: int, t: int, offset: int = 0) -> jax.Array:
    """(1,1,1,S,T) boolean mask: query i attends to keys j <= i + offset."""
    qi = jnp.arange(s)[:, None] + offset
    kj = jnp.arange(t)[None, :]
    return (kj <= qi)[None, None, None]


def self_attention(params: dict, x: jax.Array, cfg: ModelConfig, rules,
                   positions: Optional[jax.Array] = None,
                   causal: bool = True) -> jax.Array:
    """Full-sequence self attention (training / prefill)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = project_qkv(params, x, cfg, rules, positions)
    if cfg.shard_ctx_train:
        # context-parallel attention (§Perf hillclimb): shard k/v over the
        # model axis along SEQUENCE; XLA inserts the softmax/psum
        # collectives, dividing score memory and attention compute by the
        # TP degree even when head counts don't divide the mesh axis.
        k = L.constrain(k, rules, (L.BATCH, L.KV_SEQ, L.KV_HEADS,
                                   L.HEAD_DIM))
        v = L.constrain(v, rules, (L.BATCH, L.KV_SEQ, L.KV_HEADS,
                                   L.HEAD_DIM))
    if cfg.attn_impl == "flash" and causal:
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(q, k, v, causal=True)
    else:
        mask = causal_mask(s, s) if causal else None
        out = gqa_attend(q, k, v, mask, cfg, rules,
                         ctx_sharded=cfg.shard_ctx_train)
    dt = x.dtype
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return L.constrain(out, rules, (L.BATCH, L.SEQ, L.ACT_EMBED))


def cross_attention(params: dict, x: jax.Array, memory: jax.Array,
                    cfg: ModelConfig, rules) -> jax.Array:
    """Decoder->encoder attention (enc-dec archs). No causal mask, no rope."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", memory, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", memory, params["wv"].astype(dt))
    out = gqa_attend(q, k, v, None, cfg, rules)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return L.constrain(out, rules, (L.BATCH, L.SEQ, L.ACT_EMBED))


# ---------------------------------------------------------------------------
# KV-cache decode path (TextIsland / KVStore engine feeds these tensors)
# ---------------------------------------------------------------------------
def kv_cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    kv_axes = (L.BATCH, L.KV_SEQ, L.KV_HEADS, L.HEAD_DIM)
    if cfg.kv_cache_dtype == "int8":
        # quant_cast pages (the Migrator's int8 binary cast applied to the
        # serving cache): 1B/elem + one f32 scale per (token, head)
        sc_axes = (L.BATCH, L.KV_SEQ, L.KV_HEADS, None)
        return {
            "k": ParamSpec((batch, cache_len, hkv, dh), kv_axes,
                           dtype=jnp.int8, init="zeros"),
            "v": ParamSpec((batch, cache_len, hkv, dh), kv_axes,
                           dtype=jnp.int8, init="zeros"),
            "k_scale": ParamSpec((batch, cache_len, hkv, 1), sc_axes,
                                 dtype=jnp.float32, init="zeros"),
            "v_scale": ParamSpec((batch, cache_len, hkv, 1), sc_axes,
                                 dtype=jnp.float32, init="zeros"),
        }
    return {
        "k": ParamSpec((batch, cache_len, hkv, dh), kv_axes,
                       dtype=jnp.bfloat16, init="zeros"),
        "v": ParamSpec((batch, cache_len, hkv, dh), kv_axes,
                       dtype=jnp.bfloat16, init="zeros"),
    }


def _quant_heads(x: jax.Array):
    """Per-(token, head) int8 quantization of (B,S,H,Dh) k/v tensors."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                     keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _dequant_heads(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def write_kv(cache: dict, k_new: jax.Array, v_new: jax.Array, pos,
             cfg: ModelConfig) -> dict:
    """Write a [pos, pos+S) span of k/v into the cache (codec-aware)."""
    new_cache = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_heads(k_new)
        vq, vs = _quant_heads(v_new)
        writes = (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs))
    else:
        writes = (("k", k_new), ("v", v_new))
    for name, val in writes:
        new_cache[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], val.astype(cache[name].dtype), pos, axis=1)
    return new_cache


def decode_attention(params: dict, x: jax.Array, cache: dict,
                     pos: jax.Array, cfg: ModelConfig, rules
                     ) -> Tuple[jax.Array, dict]:
    """One-token decode: write (k,v) at ``pos``, attend over cache[:pos+1].

    x: (B, 1, D); pos: scalar int32 (same position for the whole batch — the
    serve scheduler aligns slots); cache k/v: (B, T, Hkv, Dh).
    """
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = project_qkv(params, x, cfg, rules, positions)
    new_cache = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_heads(k_new)
        vq, vs = _quant_heads(v_new)
        for name, val in (("k", kq), ("v", vq), ("k_scale", ks),
                          ("v_scale", vs)):
            new_cache[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], val.astype(cache[name].dtype), pos, axis=1)
        k_att = _dequant_heads(new_cache["k"], new_cache["k_scale"],
                               q.dtype)
        v_att = _dequant_heads(new_cache["v"], new_cache["v_scale"],
                               q.dtype)
    else:
        new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
        new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
        k_att = new_cache["k"].astype(q.dtype)
        v_att = new_cache["v"].astype(q.dtype)
    t = k_att.shape[1]
    mask = (jnp.arange(t)[None, None, None, None, :] <= pos)
    out = gqa_attend(q, k_att, v_att, mask, cfg, rules)
    dt = x.dtype
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    out = L.constrain(out, rules, (L.BATCH, L.SEQ, L.ACT_EMBED))
    return out, new_cache
