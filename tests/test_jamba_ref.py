"""The jamba2-3b forward against the plain float32 reference
(``repro.models.jamba_ref``) on seeded weights, on the CPU at the
reduced preset; the float8 control fails the tolerance; Jamba's
attention applies no positional encoding."""
import jax
import jax.numpy as jnp
import pytest

from repro.models import attention, jamba_ref, registry
from repro.sharding import logical as L
from repro.stream import ml

S = 128
# Largest |program - reference| logit over the std of the reference's
# logits.  The program computes in bfloat16 (2**-8 relative per
# rounding) over 8 layers of gated products; on this preset it reads
# 0.32-0.61 (seeds 0-5), the float8 e4m3 control (2**-4 per rounding)
# 3.47-5.15.  The tolerance sits 2.5x above the one and 2.3x below the
# other.
LOGIT_TOL = 1.5


def _setup(seed):
    cfg = registry.get_config("jamba2-3b", reduced=True)
    params = L.init_params(jax.random.PRNGKey(seed), ml.weight_specs(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(100 + seed), (S,), 0,
                              cfg.vocab_size)
    return cfg, params, toks


def _err(logits, want):
    return float(jnp.max(jnp.abs(logits - want)) / jnp.std(want))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference(seed):
    cfg, params, toks = _setup(seed)
    fwd = jax.jit(lambda p, t: registry.forward(p, {"tokens": t}, cfg,
                                                None)[0])
    got = fwd(params, toks[None])[0]
    assert _err(got, jamba_ref.forward(params, toks, cfg)) < LOGIT_TOL


def test_float8_control_fails_the_tolerance():
    cfg, params, toks = _setup(0)
    want = jamba_ref.forward(params, toks, cfg)
    control = jamba_ref.forward(params, toks, cfg, low=jnp.float8_e4m3fn)
    assert _err(control, want) > LOGIT_TOL


@pytest.mark.parametrize("name,rotates", [("jamba2-3b", False),
                                          ("jamba-v0.1-52b", False),
                                          ("qwen2-1.5b", True)])
def test_jamba_attention_has_no_positional_encoding(name, rotates):
    """For the Jamba family, attention equals ``gqa_attend`` on the plain
    (unrotated) projections, bitwise; a RoPE model's does not."""
    cfg = registry.get_config(name, reduced=True)
    params = L.init_params(jax.random.PRNGKey(0), attention.attn_specs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model))
    got = attention.self_attention(params, x, cfg, None)
    q, k, v = (jnp.einsum("bsd,dhk->bshk", x, params[w])
               for w in ("wq", "wk", "wv"))
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    plain = attention.gqa_attend(q, k, v, attention.causal_mask(16, 16),
                                 cfg, None)
    plain = jnp.einsum("bshk,hkd->bsd", plain, params["wo"])
    assert bool(jnp.array_equal(got, plain)) is not rotates
