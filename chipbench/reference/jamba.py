"""Plain float32 reference of the Jamba forward pass, and the weights it
is compared on: the yardstick of the ``icu-jamba2`` cell's check.

Kept with the benchmark, so that the yardstick does not move when the
program's model code does: it imports nothing of ``repro`` and reads
the model's sizes from the configuration file's published keys
(``hidden_size``, ``mamba_d_state``, ``attn_layer_period``, ...).

The weights are the benchmark's own: ``weight`` draws each one from the
run's seed, the layer and the weight's name in this module's layout (one
dict per layer, 2-D matrices ``(inputs, outputs)`` as the Hugging Face
``jamba`` modelling stores them transposed), and rounds it to bfloat16,
the precision the configuration states.  The cell hands the same draws
to the program; the reference draws them again, one layer at a time, so
at the published widths it fits beside nothing else once the program's
state is freed.  A fault in how the program makes, lays out or holds
its weights therefore shows in the comparison.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, after arXiv:2403.19887 and
the HF modelling: token embedding, then per layer a pre-norm mixer (a
Mamba-1 selective scan stepped one position at a time, or causal
multi-query attention with no positional encoding) and a pre-norm
SwiGLU MLP, each added to the residual; a final RMSNorm and the tied
head.  One sequence; no cache, batching or kernels.  ``low`` rounds
every weight and every matmul input to a lower precision (float8 e4m3
for the control).

Departures from the paper and the HF modelling: a dense MLP on every
layer (Jamba2-3B's ``num_experts: 1``, no MoE); random weights, not
trained ones: fan-in scaled normals, norm scales and the skip ``D``
near 1, Mamba's own A (S4D-real) and dt bias (softplus of it
log-uniform in [1e-3, 1e-1], arXiv:2312.00752 section 3.6).
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Sizes(NamedTuple):
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    state: int
    conv: int
    dt_rank: int
    eps: float
    attn_period: int
    attn_offset: int


def sizes(cfg: Dict[str, Any]) -> Sizes:
    """The reference's sizes from the configuration's published keys."""
    return Sizes(layers=cfg["num_hidden_layers"],
                 heads=cfg["num_attention_heads"],
                 kv_heads=cfg["num_key_value_heads"],
                 head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                 state=cfg["mamba_d_state"], conv=cfg["mamba_d_conv"],
                 dt_rank=cfg["mamba_dt_rank"], eps=cfg["rms_norm_eps"],
                 attn_period=cfg["attn_layer_period"],
                 attn_offset=cfg["attn_layer_offset"])


def tokens(values: np.ndarray, vocab: int) -> np.ndarray:
    """A window's token ids: min/max binning of its float64 values into
    ``vocab`` ids (the bdml island's tokenizer, as documented)."""
    v = np.asarray(values, np.float64).reshape(-1)
    lo, hi = float(v.min()), float(v.max())
    if hi <= lo:
        return np.zeros(v.shape[0], np.int32)
    ids = np.floor((v - lo) / (hi - lo) * (vocab - 1))
    return np.minimum(ids, vocab - 1).astype(np.int32)


def score(logits, toks: np.ndarray) -> float:
    """Mean next-token negative log-likelihood in nats, in float64."""
    lg = np.asarray(logits, np.float64)[:-1]
    m = lg.max(-1, keepdims=True)
    lse = m[:, 0] + np.log(np.exp(lg - m).sum(-1))
    return float(np.mean(lse - lg[np.arange(lg.shape[0]), toks[1:]]))


def _mm(a, w, low):
    return _low(a, low) @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _mlp(p, x, low):
    return _mm(jax.nn.silu(_mm(x, p["wi_gate"], low))
               * _mm(x, p["wi_up"], low), p["wo"], low)


def _mamba(p, x, z: Sizes, low):
    s = x.shape[0]
    r, n, cw = z.dt_rank, z.state, z.conv
    u, gate = jnp.split(_mm(x, p["in_proj"], low), 2, axis=-1)
    up = jnp.concatenate([jnp.zeros((cw - 1, u.shape[1])), u])
    u = jax.nn.silu(sum(up[i:i + s] * p["conv_w"][i] for i in range(cw))
                    + p["conv_b"])
    dt, b, c = jnp.split(_mm(u, p["x_proj"], low), [r, r + n], axis=-1)
    dt = jax.nn.softplus(
        _mm(_rms(dt, p["dt_norm"], z.eps), p["dt_proj"], low) + p["dt_bias"])
    b = _rms(b, p["b_norm"], z.eps)
    c = _rms(c, p["c_norm"], z.eps)
    a = -jnp.exp(p["a_log"])

    def step(h, t):
        h = jnp.exp(dt[t][:, None] * a) * h \
            + (dt[t] * u[t])[:, None] * b[t][None, :]
        return h, h @ c[t]

    _, y = jax.lax.scan(step, jnp.zeros(a.shape), jnp.arange(s))
    return _mm((y + u * p["d_skip"]) * jax.nn.silu(gate), p["out_proj"], low)


def _attention(p, x, z: Sizes, low):
    s = x.shape[0]
    h, hkv, hd = z.heads, z.kv_heads, z.head_dim
    q = _mm(x, p["wq"], low).reshape(s, hkv, h // hkv, hd)
    k = _mm(x, p["wk"], low).reshape(s, hkv, hd)
    v = _mm(x, p["wv"], low).reshape(s, hkv, hd)
    att = jnp.einsum("sngd,tnd->ngst", _low(q, low), _low(k, low)) \
        / jnp.sqrt(float(hd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    out = jnp.einsum("ngst,tnd->sngd", _low(att, low), _low(v, low))
    return _mm(out.reshape(s, h * hd), p["wo"], low)


@functools.partial(jax.jit, static_argnames=("kind", "z", "low"))
def _layer(p, x, *, kind: str, z: Sizes, low):
    mixer = _attention if kind == "attn" else _mamba
    x = x + mixer(p["mixer"], _rms(x, p["ln1"], z.eps), z, low)
    return x + _mlp(p["ffn"], _rms(x, p["ln2"], z.eps), low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(scale, table, x, *, eps: float, low):
    return _mm(_rms(x, scale, eps), table.T, low)


def kind(z: Sizes, layer: int) -> str:
    """``attn`` or ``mamba``: the mixer of ``layer``."""
    return "attn" if layer % z.attn_period == z.attn_offset else "mamba"


def shapes(cfg: Dict[str, Any], layer: int) -> Dict[str, Tuple]:
    """{name: (shape, how it is drawn)} of the weights of ``layer``, or
    of the embedding and the final norm for ``layer`` -1."""
    z = sizes(cfg)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    if layer < 0:
        return {"embed": ((cfg["vocab_size"], d), "embed"),
                "final_norm": ((d,), "scale")}
    out = {"ln1": ((d,), "scale"), "ln2": ((d,), "scale"),
           "ffn.wi_gate": ((d, f), "fan_in"), "ffn.wi_up": ((d, f), "fan_in"),
           "ffn.wo": ((f, d), "fan_in")}
    if kind(z, layer) == "attn":
        h, hkv = z.heads * z.head_dim, z.kv_heads * z.head_dim
        out.update({"mixer.wq": ((d, h), "fan_in"),
                    "mixer.wk": ((d, hkv), "fan_in"),
                    "mixer.wv": ((d, hkv), "fan_in"),
                    "mixer.wo": ((h, d), "fan_in")})
        return out
    di, r, n = cfg["mamba_expand"] * d, z.dt_rank, z.state
    out.update({"mixer.in_proj": ((d, 2 * di), "fan_in"),
                "mixer.conv_w": ((z.conv, di), "fan_in"),
                "mixer.conv_b": ((di,), "bias"),
                "mixer.x_proj": ((di, r + 2 * n), "fan_in"),
                "mixer.dt_proj": ((r, di), "fan_in"),
                "mixer.dt_bias": ((di,), "dt_bias"),
                "mixer.a_log": ((di, n), "a_log"),
                "mixer.d_skip": ((di,), "scale"),
                "mixer.out_proj": ((di, d), "fan_in"),
                "mixer.dt_norm": ((r,), "scale"),
                "mixer.b_norm": ((n,), "scale"),
                "mixer.c_norm": ((n,), "scale")})
    return out


def weight(cfg: Dict[str, Any], seed: int, layer: int,
           name: str) -> jax.Array:
    """One weight, bfloat16, drawn from ``seed``, ``layer`` and ``name``
    alone: the same arguments give the same bits."""
    shape, how = shapes(cfg, layer)[name]
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), layer + 1), zlib.crc32(name.encode())
        & 0x7FFFFFFF)
    normal = functools.partial(jax.random.normal, key, shape)
    if how == "fan_in":
        w = normal() / np.sqrt(shape[0])
    elif how == "embed":
        w = 0.02 * normal()
    elif how == "scale":
        w = 1.0 + 0.1 * normal()
    elif how == "bias":
        w = 0.1 * normal()
    elif how == "a_log":
        w = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1,
                                                dtype=jnp.float32)), shape)
    else:                                    # dt_bias
        lo, hi = np.log(1e-3), np.log(1e-1)
        dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape))
        w = dt + jnp.log(-jnp.expm1(-dt))
    return w.astype(jnp.bfloat16)


def layer_weights(cfg: Dict[str, Any], seed: int, layer: int,
                  low=None) -> Dict[str, Any]:
    """``layer``'s weights as nested dicts (``mixer``/``ffn`` groups),
    float32 (rounded to ``low`` first)."""
    out: Dict[str, Any] = {}
    for name in shapes(cfg, layer):
        group, _, leaf = name.rpartition(".")
        (out.setdefault(group, {}) if group else out)[leaf] = _low(
            weight(cfg, seed, layer, name), low)
    return out


def _low(x, low):
    x = x.astype(low) if low is not None else x
    return x.astype(jnp.float32)


def forward(cfg: Dict[str, Any], seed: int, toks,
            low: Optional[Any] = None) -> jax.Array:
    """Logits (S, V) float32 of one token sequence ``toks`` (S,) under the
    weights drawn from ``seed``."""
    z = sizes(cfg)
    with jax.default_matmul_precision("highest"):
        table = _low(weight(cfg, seed, -1, "embed"), low)
        x = table[jnp.asarray(toks)]
        for layer in range(z.layers):
            p = layer_weights(cfg, seed, layer, low)
            x = _layer(p, x, kind=kind(z, layer), z=z, low=low)
            del p
        return _head(_low(weight(cfg, seed, -1, "final_norm"), low), table,
                     x, eps=z.eps, low=low)


def flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Model FLOPs (a multiply-add counts 2) of one token of a forward
    over ``seq`` tokens, from the configuration's published keys: the
    projections, MLPs and tied head, causal attention over the
    ``(seq + 1) / 2`` keys a token sees on average, and the selective
    scan's elementwise work (``exp(dt A) h + dt u B`` and ``h C`` over
    the state, the skip and the gate)."""
    z = sizes(cfg)
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    di = cfg["mamba_expand"] * d
    h, hkv, hd = z.heads, z.kv_heads, z.head_dim
    attn_layers = sum(layer % z.attn_period == z.attn_offset
                      for layer in range(z.layers))
    mlp = 2 * 3 * d * f
    attn = 2 * d * (h + 2 * hkv) * hd + 2 * h * hd * d \
        + 4 * h * hd * (seq + 1) / 2
    mamba = 2 * (d * 2 * di + z.conv * di + di * (z.dt_rank + 2 * z.state)
                 + z.dt_rank * di + di * d) + 7 * di * z.state + 4 * di
    return (z.layers * mlp + attn_layers * attn
            + (z.layers - attn_layers) * mamba + 2 * d * v)
