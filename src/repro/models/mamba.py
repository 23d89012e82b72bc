"""Mamba (selective SSM) mixer as used in Jamba (arXiv:2403.19887).

The selective scan runs through ``kernels/mamba_scan/ops.scan``: the
lane-dense Pallas kernel when compiled for a TPU over at least one chunk
of steps and whole 128-lane channel tiles (``ops.uses_kernel``), the
reference ``lax.scan`` (``kernels/mamba_scan/ref.py``) everywhere else:
on the CPU, in the tests' interpreter, and in decode at S = 1, an O(1)
state update.  Gradients through the kernel take the reference's VJP.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.sharding import logical as L
from repro.sharding.logical import ParamSpec


def d_inner(cfg: ModelConfig) -> int:
    return cfg.ssm_expand * cfg.d_model


def dt_rank(cfg: ModelConfig) -> int:
    return math.ceil(cfg.d_model / 16)


def mamba_specs(cfg: ModelConfig) -> dict:
    d, di, n, r, cw = (cfg.d_model, d_inner(cfg), cfg.ssm_state,
                       dt_rank(cfg), cfg.ssm_conv)
    return {
        "in_proj": ParamSpec((d, 2 * di), (L.EMBED, L.MLP)),
        "conv_w": ParamSpec((cw, di), (L.CONV, L.MLP), init="normal"),
        "conv_b": ParamSpec((di,), (L.MLP,), init="zeros"),
        "x_proj": ParamSpec((di, r + 2 * n), (L.MLP, None)),
        "dt_proj": ParamSpec((r, di), (None, L.MLP)),
        "dt_bias": ParamSpec((di,), (L.MLP,), init="ssm_dt_bias"),
        "a_log": ParamSpec((di, n), (L.MLP, L.STATE), init="ssm_a_log"),
        "d_skip": ParamSpec((di,), (L.MLP,), init="ones"),
        "out_proj": ParamSpec((di, d), (L.MLP, L.EMBED)),
        # Jamba stabilizes dt/B/C with RMSNorm scales
        "dt_norm": ParamSpec((r,), (None,), init="ones"),
        "b_norm": ParamSpec((n,), (L.STATE,), init="ones"),
        "c_norm": ParamSpec((n,), (L.STATE,), init="ones"),
    }


def init_mamba_state(cfg: ModelConfig, batch: int) -> dict:
    di, n, cw = d_inner(cfg), cfg.ssm_state, cfg.ssm_conv
    return {
        "h": ParamSpec((batch, di, n), (L.BATCH, L.MLP, L.STATE),
                       dtype=jnp.float32, init="zeros"),
        "conv": ParamSpec((batch, cw - 1, di), (L.BATCH, L.CONV, L.MLP),
                          dtype=jnp.bfloat16, init="zeros"),
    }


def _rms(x, scale, eps=1e-5):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 prev: Optional[jax.Array]) -> jax.Array:
    """Depthwise causal conv1d.  x: (B,S,Di); w: (CW,Di); prev: (B,CW-1,Di)."""
    cw = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)           # (B, S+CW-1, Di)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None]
              for i in range(cw))
    return out + b[None, None]


def apply_mamba(params: dict, x: jax.Array, cfg: ModelConfig, rules,
                state: Optional[dict] = None
                ) -> Tuple[jax.Array, Optional[dict]]:
    bsz, s, d = x.shape
    di, n, r = d_inner(cfg), cfg.ssm_state, dt_rank(cfg)
    dt_ = x.dtype

    xz = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(dt_))
    xz = L.constrain(xz, rules, (L.BATCH, L.SEQ, L.MLP))
    xin, z = jnp.split(xz, 2, axis=-1)

    prev_conv = state["conv"].astype(dt_) if state is not None else None
    xc = _causal_conv(xin, params["conv_w"].astype(dt_),
                      params["conv_b"].astype(dt_), prev_conv)
    xc = jax.nn.silu(xc)
    xc = L.constrain(xc, rules, (L.BATCH, L.SEQ, L.MLP))

    proj = jnp.einsum("bse,ep->bsp", xc, params["x_proj"].astype(dt_))
    dt_low, b_in, c_in = jnp.split(proj, [r, r + n], axis=-1)
    dt_low = _rms(dt_low, params["dt_norm"], cfg.norm_eps)
    b_in = _rms(b_in, params["b_norm"], cfg.norm_eps)
    c_in = _rms(c_in, params["c_norm"], cfg.norm_eps)
    dt_full = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dt_low, params["dt_proj"].astype(dt_))
        + params["dt_bias"].astype(dt_))

    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    h0 = (state["h"] if state is not None
          else jnp.zeros((bsz, di, n), jnp.float32))
    # imported here, as attention.py imports its flash kernel: a process
    # that runs no Mamba forward does not load Pallas
    from repro.kernels.mamba_scan import ops as scan_ops
    y, h_final = scan_ops.scan(xc, dt_full, a, b_in, c_in, h0)
    y = y.astype(dt_) + xc * params["d_skip"].astype(dt_)[None, None]

    out = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", out, params["out_proj"].astype(dt_))
    out = L.constrain(out, rules, (L.BATCH, L.SEQ, L.ACT_EMBED))

    new_state = None
    if state is not None:
        tail = jnp.concatenate([prev_conv, xin], axis=1)[:, -(cfg.ssm_conv - 1):]
        new_state = {"h": h_final, "conv": tail.astype(jnp.bfloat16)}
    return out, new_state
