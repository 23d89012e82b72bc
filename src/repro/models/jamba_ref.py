"""Plain float32 reference of the Jamba forward pass, for tests.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, after arXiv:2403.19887 and
the Hugging Face ``jamba`` modelling: token embedding, then per layer a
pre-norm mixer (a Mamba-1 selective scan stepped one position at a time,
or causal multi-query attention with no positional encoding) and a
pre-norm SwiGLU MLP, each added to the residual; a final RMSNorm and the
tied head.  One sequence; no cache, batching or kernels.

It reads the program's weights (the ``repro.models.lm`` tree: the layers
are scanned blocks of ``P`` sub-layers, so layer ``l`` is block
``l // P``, sub-layer ``l % P``) and upcasts them to float32 one layer at
a time, so at published widths it needs one layer's float32 copy beside
the program's bfloat16 weights.

``low`` (a dtype such as ``jnp.float8_e4m3fn``) rounds every weight and
every matmul input to that precision: the control that a comparison's
tolerance must reject.

Departures from the paper and the HF modelling:
  * a dense MLP on every layer, as Jamba2-3B's ``num_experts: 1``; the
    MoE layers of Jamba v0.1 are not here;
  * the weights are the program's seeded draws (fan-in scaled normals;
    A and the dt bias as Mamba initialises them), not trained weights;
  * the layer kind follows ``attn_every``/``attn_offset``, HF's
    ``attn_layer_period``/``attn_layer_offset``.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def _low(x, low):
    """``x`` in float32, rounded through ``low`` first when given."""
    x = x.astype(low) if low is not None else x
    return x.astype(jnp.float32)


def _mm(a, w, low):
    return _low(a, low) @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _mlp(p, x, low):
    return _mm(jax.nn.silu(_mm(x, p["wi_gate"], low))
               * _mm(x, p["wi_up"], low), p["wo"], low)


def _mamba(p, x, cfg: ModelConfig, low):
    """Mamba-1 mixer of one sequence ``x`` (S, D)."""
    s = x.shape[0]
    n, cw = cfg.ssm_state, cfg.ssm_conv
    r = p["dt_proj"].shape[0]
    u, z = jnp.split(_mm(x, p["in_proj"], low), 2, axis=-1)
    # depthwise causal conv1d with bias
    up = jnp.concatenate([jnp.zeros((cw - 1, u.shape[1])), u])
    u = sum(up[i:i + s] * p["conv_w"][i] for i in range(cw)) + p["conv_b"]
    u = jax.nn.silu(u)
    dt, b, c = jnp.split(_mm(u, p["x_proj"], low), [r, r + n], axis=-1)
    dt = _rms(dt, p["dt_norm"], cfg.norm_eps)
    b = _rms(b, p["b_norm"], cfg.norm_eps)
    c = _rms(c, p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(_mm(dt, p["dt_proj"], low) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])                          # (Di, N)

    def step(h, t):
        # h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t ;  y_t = h_t C_t
        h = jnp.exp(dt[t][:, None] * a) * h \
            + (dt[t] * u[t])[:, None] * b[t][None, :]
        return h, h @ c[t]

    _, y = jax.lax.scan(step, jnp.zeros(a.shape), jnp.arange(s))
    y = (y + u * p["d_skip"]) * jax.nn.silu(z)
    return _mm(y, p["out_proj"], low)


def _attention(p, x, cfg: ModelConfig, low):
    """Causal multi-query attention of ``x`` (S, D), no RoPE."""
    s, d = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _mm(x, p["wq"].reshape(d, h * hd), low).reshape(s, hkv, h // hkv,
                                                         hd)
    k = _mm(x, p["wk"].reshape(d, hkv * hd), low).reshape(s, hkv, hd)
    v = _mm(x, p["wv"].reshape(d, hkv * hd), low).reshape(s, hkv, hd)
    scores = jnp.einsum("sngd,tnd->ngst", _low(q, low), _low(k, low)) \
        / jnp.sqrt(float(hd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("ngst,tnd->sngd", _low(probs, low), _low(v, low))
    return _mm(out.reshape(s, h * hd), p["wo"].reshape(h * hd, d), low)


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "low"))
def _layer(p, x, *, kind: str, cfg: ModelConfig, low):
    mixer = _attention if kind == "attn" else _mamba
    x = x + mixer(p["mixer"], _rms(x, p["ln1"]["scale"], cfg.norm_eps),
                  cfg, low)
    return x + _mlp(p["ffn"], _rms(x, p["ln2"]["scale"], cfg.norm_eps),
                    low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(scale, table, x, *, eps: float, low):
    return _mm(_rms(x, scale, eps), table.T, low)


def layer_kind(cfg: ModelConfig, layer: int) -> str:
    return "attn" if layer % cfg.attn_every == cfg.attn_offset else "mamba"


def forward(params: Any, tokens, cfg: ModelConfig,
            low: Optional[Any] = None) -> jax.Array:
    """Logits (S, V) float32 of one token sequence ``tokens`` (S,)."""
    up = functools.partial(_low, low=low)
    blocks = params["blocks"]
    period = len(blocks)
    with jax.default_matmul_precision("highest"):
        table = up(params["embed"]["embedding"])
        x = table[jnp.asarray(tokens)]
        for layer in range(cfg.num_layers):
            sub = blocks[f"sub{layer % period}"]
            p = jax.tree.map(lambda a: up(a[layer // period]), sub)
            x = _layer(p, x, kind=layer_kind(cfg, layer), cfg=cfg, low=low)
            del p
        return _head(up(params["final_norm"]["scale"]), table, x,
                     eps=cfg.norm_eps, low=low)
