"""Architecture registry: maps --arch ids to configs and provides the
uniform batch-dict model API used by train/serve/launch.

Batch dicts (data pipeline & input_specs produce exactly these):
  train:   {tokens (B,S_text) i32, labels (B,S_text) i32
            [, prefix_embeds (B,P,D) f32]            # vlm stub frontend
            [, frame_embeds (B,S_src,D) f32]}        # audio stub frontend
  prefill: {tokens (B,S)} (+ stubs) + cache pytree
  decode:  {tokens (B,1)} + cache pytree + pos scalar
            (+ memory (B,S_src,D) for enc-dec)
"""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, lm
from repro.models.config import ModelConfig

_ARCH_MODULES = {
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "command-r-plus-104b": "repro.configs.command_r_plus_104b",
    "command-r-35b": "repro.configs.command_r_35b",
    "deepseek-coder-33b": "repro.configs.deepseek_coder_33b",
    "qwen2-1.5b": "repro.configs.qwen2_1_5b",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "jamba-v0.1-52b": "repro.configs.jamba_v0_1_52b",
    "jamba2-3b": "repro.configs.jamba2_3b",
}

ARCH_NAMES = tuple(_ARCH_MODULES)


def get_config(name: str, *, reduced: bool = False, **overrides
               ) -> ModelConfig:
    mod = importlib.import_module(_ARCH_MODULES[name])
    cfg = mod.reduced() if reduced else mod.CONFIG
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def is_encdec(cfg: ModelConfig) -> bool:
    return cfg.encoder_layers > 0


def param_specs(cfg: ModelConfig):
    return encdec.param_specs(cfg) if is_encdec(cfg) else lm.param_specs(cfg)


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int):
    if is_encdec(cfg):
        return encdec.cache_specs(cfg, batch, cache_len)
    return lm.cache_specs(cfg, batch, cache_len)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    if is_encdec(cfg):
        return encdec.init_cache(cfg, batch, cache_len)
    return lm.init_cache(cfg, batch, cache_len)


def forward(params, batch: Dict[str, Any], cfg: ModelConfig, rules=None
            ) -> Tuple[jax.Array, jax.Array]:
    if is_encdec(cfg):
        return encdec.forward(params, batch["tokens"],
                              batch["frame_embeds"], cfg, rules)
    return lm.forward(params, batch["tokens"], cfg, rules,
                      prefix_embeds=batch.get("prefix_embeds"))


def prefill(params, batch: Dict[str, Any], cache, cfg: ModelConfig,
            rules=None):
    """Returns (last-token logits, cache, extras-dict)."""
    if is_encdec(cfg):
        logits, new_cache, memory = encdec.prefill(
            params, batch["tokens"], batch["frame_embeds"], cache, cfg,
            rules)
        return logits, new_cache, {"memory": memory}
    logits, new_cache = lm.prefill(params, batch["tokens"], cache, cfg,
                                   rules,
                                   prefix_embeds=batch.get("prefix_embeds"))
    return logits, new_cache, {}


def decode_step(params, batch: Dict[str, Any], cache, pos,
                cfg: ModelConfig, rules=None):
    if is_encdec(cfg):
        return encdec.decode_step(params, batch["tokens"], batch["memory"],
                                  cache, pos, cfg, rules)
    return lm.decode_step(params, batch["tokens"], cache, pos, cfg, rules)


def loss_fn(logits: jax.Array, labels: jax.Array, aux: jax.Array,
            aux_weight: float = 0.01) -> jax.Array:
    """Next-token CE over the last S_text positions (+ MoE aux loss)."""
    s_text = labels.shape[1]
    logits = logits[:, -s_text:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean() + aux_weight * aux


def text_len(cfg: ModelConfig, seq_len: int) -> int:
    """Text positions for a cell's total sequence length."""
    if cfg.frontend == "vision":
        return seq_len - cfg.num_prefix_embeds
    return seq_len


def make_train_batch(cfg: ModelConfig, seq_len: int, batch: int, key=None
                     ) -> Dict[str, Any]:
    """Materialized random batch (CPU smoke tests / examples)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    st = text_len(cfg, seq_len)
    out = {
        "tokens": jax.random.randint(k1, (batch, st), 0, cfg.vocab_size,
                                     dtype=jnp.int32),
        "labels": jax.random.randint(k2, (batch, st), 0, cfg.vocab_size,
                                     dtype=jnp.int32),
    }
    if cfg.frontend == "vision":
        out["prefix_embeds"] = jax.random.normal(
            k3, (batch, cfg.num_prefix_embeds, cfg.d_model),
            dtype=jnp.float32)
    if cfg.frontend == "audio":
        out["frame_embeds"] = jax.random.normal(
            k3, (batch, max(1, seq_len // cfg.src_ratio), cfg.d_model),
            dtype=jnp.float32)
    return out
