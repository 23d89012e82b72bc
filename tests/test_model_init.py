"""The seeded weight init: fan-in is the product of a weight's input
axes, so attention at published widths starts with logits of order one,
and the Mamba scan starts from Mamba's own A and dt."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention, mamba, registry
from repro.sharding import logical as L


@pytest.mark.parametrize("shape,axes,want", [
    ((2560, 20, 128), (L.EMBED, L.HEADS, L.HEAD_DIM), 2560),       # wq
    ((2560, 1, 128), (L.EMBED, L.KV_HEADS, L.HEAD_DIM), 2560),     # wk
    ((20, 128, 2560), (L.HEADS, L.HEAD_DIM, L.EMBED), 20 * 128),   # wo
    ((2, 2560, 20, 128), (L.LAYER, L.EMBED, L.HEADS, L.HEAD_DIM), 2560),
    ((2, 20, 128, 2560), (L.LAYER, L.HEADS, L.HEAD_DIM, L.EMBED), 2560),
    ((2560, 8192), (L.EMBED, L.MLP), 2560),
    ((4, 2560, 8192), (L.LAYER, L.EMBED, L.MLP), 2560),
    ((16, 2048, 1024), (L.EXPERT, L.EMBED, None), 2048),
    ((4, 5120), (L.CONV, L.MLP), 4),
    ((7,), (None,), 7),
], ids=["wq", "wk", "wo", "wq-stacked", "wo-stacked", "2d", "2d-stacked",
        "experts", "conv", "1d"])
def test_fan_in_is_the_product_of_the_input_axes(shape, axes, want):
    assert L.fan_in(L.ParamSpec(shape, axes)) == want


@pytest.mark.parametrize("name", ["jamba2-3b", "qwen2-1.5b"])
def test_attention_logits_are_order_one_at_published_widths(name):
    """One attention layer at the published widths, on RMS-normed rows
    of an ``embed_normal`` draw (what layer 0 sees): logits' std is of
    order one (it was ~431 for qwen2-1.5b with fan-in = shape[-2])."""
    cfg = registry.get_config(name)
    params = L.init_params(jax.random.PRNGKey(0), attention.attn_specs(cfg))
    x = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    q, k, _ = attention.project_qkv(params, x, cfg, None,
                                    jnp.arange(64)[None])
    groups = cfg.num_heads // cfg.num_kv_heads
    q = q.reshape(1, 64, cfg.num_kv_heads, groups, cfg.head_dim)
    logits = jnp.einsum("bsngd,btnd->bngst", q, k) * cfg.head_dim ** -0.5
    assert 0.5 < float(jnp.std(logits)) < 2.0


def test_mamba_scan_starts_from_mambas_a_and_dt():
    cfg = registry.get_config("jamba2-3b", reduced=True)
    p = L.init_params(jax.random.PRNGKey(0), mamba.mamba_specs(cfg))
    a = -np.exp(np.asarray(p["a_log"]))
    np.testing.assert_allclose(
        a, -np.broadcast_to(np.arange(1, cfg.ssm_state + 1), a.shape),
        rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1e-3 * (1 - 1e-5) <= dt.min() and dt.max() <= 1e-1 * (1 + 1e-5)
    assert dt.max() / dt.min() > 10            # spread over the decades
