"""Per-architecture smoke tests (deliverable (f)): every assigned arch
instantiates a REDUCED config of the same family and runs one forward +
one train step on CPU, asserting output shapes and no NaNs.  The FULL
configs are exercised only via the dry-run (ShapeDtypeStruct, no alloc).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.models import registry
from repro.optim.adamw import AdamWConfig
from repro.sharding import logical as L
from repro.train.step import TrainConfig, init_train_state, make_train_step

ARCHS = list(registry.ARCH_NAMES)
SEQ, BATCH = 32, 2


@pytest.fixture(scope="module")
def states():
    return {}


def _state_for(name):
    cfg = registry.get_config(name, reduced=True)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    return cfg, state


@pytest.mark.parametrize("name", ARCHS)
def test_forward_shapes_and_finite(name):
    cfg, state = _state_for(name)
    batch = registry.make_train_batch(cfg, SEQ, BATCH)
    logits, aux = registry.forward(state["params"], batch, cfg, None)
    s_text = registry.text_len(cfg, SEQ)
    total = SEQ if cfg.frontend != "vision" else SEQ
    assert logits.shape == (BATCH, total, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{name}: non-finite logits"
    assert bool(jnp.isfinite(aux)), f"{name}: non-finite aux"


@pytest.mark.parametrize("name", ARCHS)
def test_train_step_updates_and_finite(name):
    cfg, state = _state_for(name)
    tcfg = TrainConfig(optimizer=AdamWConfig(total_steps=10,
                                             warmup_steps=2))
    step = jax.jit(make_train_step(cfg, tcfg))
    batch = jax.tree.map(jnp.asarray,
                         registry.make_train_batch(cfg, SEQ, BATCH))
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # at least one parameter changed
    before = jax.tree.leaves(state["params"])
    after = jax.tree.leaves(new_state["params"])
    changed = any(bool(jnp.any(a != b)) for a, b in zip(before, after))
    assert changed, f"{name}: no parameter update"
    assert int(new_state["opt"]["step"]) == 1


@pytest.mark.parametrize("name", ARCHS)
def test_param_count_nonzero_and_spec_axes(name):
    cfg = registry.get_config(name, reduced=True)
    specs = registry.param_specs(cfg)
    n = L.count_params(specs)
    assert n > 1000
    for leaf in jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, L.ParamSpec)):
        assert len(leaf.shape) == len(leaf.axes)


@pytest.mark.parametrize("name", ARCHS)
def test_full_config_matches_assignment(name):
    """The FULL configs carry the exact published hyper-parameters."""
    cfg = registry.get_config(name)
    expected = {
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "jamba2-3b": (28, 2560, 20, 1, 8192, 65536),
    }[name]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (name, got, expected)


def test_moe_details():
    olmoe = registry.get_config("olmoe-1b-7b")
    assert (olmoe.num_experts, olmoe.top_k) == (64, 8)
    dsm = registry.get_config("deepseek-moe-16b")
    assert (dsm.num_experts, dsm.top_k, dsm.num_shared_experts) == (64, 6, 2)
    jamba = registry.get_config("jamba-v0.1-52b")
    assert (jamba.num_experts, jamba.top_k) == (16, 2)
    assert jamba.layer_plan()[4][0] == "attn"       # 1:7 attn interleave
    assert sum(m == "attn" for m, _ in jamba.layer_plan()) == 1
    assert sum(f == "moe" for _, f in jamba.layer_plan()) == 4


def test_jamba2_details():
    """Jamba2-3B: attention at layers 7 and 21 with one KV head and no
    RoPE, Mamba everywhere else, a dense MLP on every layer."""
    cfg = registry.get_config("jamba2-3b")
    kinds = [m for _ in range(cfg.num_scanned())
             for m, _ in cfg.layer_plan()]
    assert [i for i, m in enumerate(kinds) if m == "attn"] == [7, 21]
    assert kinds.count("mamba") == 26
    assert {f for _, f in cfg.layer_plan()} == {"dense"}
    assert (cfg.num_kv_heads, cfg.norm_eps, cfg.tie_embeddings) == \
        (1, 1e-6, True)
    assert not cfg.uses_rope and registry.get_config("qwen2-1.5b").uses_rope
    assert L.count_params(registry.param_specs(cfg)) == 3_029_337_472


def test_long_context_applicability():
    from repro.configs.shapes import SHAPES, applicable
    long = SHAPES["long_500k"]
    runnable = [n for n in ARCHS
                if applicable(registry.get_config(n), long)[0]]
    assert sorted(runnable) == ["jamba-v0.1-52b", "jamba2-3b", "rwkv6-7b"]
