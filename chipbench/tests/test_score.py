"""The scored-bed cell (``icu-jamba2``) at CPU size: its generator,
reference and check pass on the program, fail on each fault the cell
can have and on the float8 control; and its per-layer readers."""
import jax
import pytest

from chipbench import control, harness
from chipbench.reference import jamba
from chipbench.tests import faults

CELL = "icu-jamba2"


def small():
    """(cell, config, traffic) of the cell with the reduced jamba2-3b
    preset (the ``mamba`` alias) and a 50-row window, so that every wave
    (each second tick) scores a new window."""
    c = harness.cell(CELL)
    cfg = harness.load("configs", c["config"])
    traffic = harness.load("traffic", c["traffic"])
    cfg.update(arch="mamba", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=1,
               mamba_d_state=8, mamba_dt_rank=4, num_hidden_layers=8,
               attn_layer_period=4, attn_layer_offset=2, window=50,
               ring_rows=100)
    traffic.update(beds=2, every_n_ticks=2, check_windows=3)
    return c, cfg, traffic


def run(monkeypatch, seconds=1.2, seed=2**33 + 9, limits=None):
    c, cfg, traffic = small()
    for k, v in cfg["env"].items():
        monkeypatch.setenv(k, v)
    return harness.run_cell(c, seed, seconds, False, jax.devices()[:1],
                            0.0, lambda *a: None, cfg=cfg, traffic=traffic,
                            limits=limits)


def test_score_cell_is_correct(monkeypatch):
    line = run(monkeypatch)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 2 * 2 * 3
    assert line["metrics"]["event_to_result_p95_ms"]["value"] > 0
    assert set(line["checks"]) == {"missing", "mismatch", "rerun_mismatch",
                                   "score_err", "logit_err"}
    assert line["checks"]["rerun_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", ["wrong_score", "dropped", "stale"])
def test_score_cell_catches_fault(monkeypatch, fault):
    import dataclasses
    from repro.serve.frontdoor import Subscription

    push = Subscription._push
    stale = faults.Stale()
    count = [0]

    def broken(self, tick, value):
        count[0] += 1
        if fault == "dropped":
            if count[0] % 3 == 0:
                return None
        elif fault == "stale":
            value = stale(self.sub_id, value)
        else:
            cols = dict(value.columns)
            cols["score"] = faults.altered("score", cols["score"])
            value = dataclasses.replace(value, columns=cols)
        return push(self, tick, value)

    monkeypatch.setattr(Subscription, "_push", broken)
    line = run(monkeypatch)
    assert not line["correct"], line["checks"]


def test_program_serves_the_benchmark_weights(monkeypatch):
    """The program holds, leaf for leaf and bit for bit, the weights the
    benchmark drew (``reference/jamba.py``) in its own param tree."""
    import jax.numpy as jnp
    from chipbench.generators import score
    from repro.stream import ml

    _, cfg, _ = small()
    tree = score.program_params(cfg, 5)
    loaded = ml.load_model("jamba2-3b", 5, True, params=tree)
    try:
        for a, b in zip(jax.tree.leaves(loaded.params),
                        jax.tree.leaves(tree)):
            assert a.dtype == jnp.bfloat16 and bool(jnp.array_equal(a, b))
        sub = loaded.params["blocks"]["sub2"]["mixer"]    # attention, layer 6
        want = jamba.weight(cfg, 5, 6, "mixer.wq")
        assert bool(jnp.array_equal(sub["wq"][1].reshape(want.shape), want))
    finally:
        ml.unload_model("jamba2-3b", 5, True)


@pytest.mark.parametrize("fault", ["layers_swapped", "own_draw"])
def test_score_cell_catches_wrong_weights(monkeypatch, fault):
    """The program serving other weights than it was handed (two layers'
    MLP output swapped; its own seeded draw in their place) fails the
    comparison with the reference."""
    from repro.sharding import logical
    from repro.stream import ml

    given = ml._given_params

    def broken(arch, params, specs):
        held = given(arch, params, specs)
        if fault == "own_draw":
            return logical.init_params(jax.random.PRNGKey(0), specs)
        wo = held["blocks"]["sub0"]["ffn"]["wo"]
        held["blocks"]["sub0"]["ffn"]["wo"] = wo[::-1]
        return held

    monkeypatch.setattr(ml, "_given_params", broken)
    line = run(monkeypatch)
    assert not line["correct"], line["checks"]


def test_score_control_fails_its_limits(monkeypatch):
    c, cfg, traffic = small()
    for k, v in cfg["env"].items():
        monkeypatch.setenv(k, v)
    [(_, prog, ctrl)] = control.readings(
        c, [2**40 + 3], 1.2, 1, jax.devices()[:1], lambda *a: None,
        cfg=cfg, traffic=traffic)
    limits = harness.load("limits", CELL)
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert any(ctrl[k] > limits[k] for k in limits), ctrl


def test_flops_per_token_of_the_published_config():
    cfg = harness.load("configs", "jamba2-3b")
    # 28 SwiGLU MLPs, 26 Mamba mixers, 2 attention layers, the tied head
    mlp = 28 * 6 * 2560 * 8192
    mamba = 26 * (2 * (2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120
                       + 5120 * 2560) + 7 * 5120 * 16 + 4 * 5120)
    attn = 2 * (2 * 2560 * 22 * 128 + 2 * 2560 * 2560
                + 4 * 20 * 128 * 2049 / 2)
    assert jamba.flops_per_token(cfg, 2048) == \
        pytest.approx(mlp + mamba + attn + 2 * 2560 * 65536)
    assert 6.0e9 < jamba.flops_per_token(cfg, 2048) < 6.2e9


def _ctx(spans=(), registry=None, window_s=2.0, config=None):
    return {"spans": list(spans), "registry": registry or {},
            "window_s": window_s, "work": {"ticks": 10},
            "config": config or harness.load("configs", "jamba2-3b"),
            "device_kind": "TPU v5 lite", "device": None}


def test_mfu_reads_the_tokens_scored():
    tokens = {"repro_ml_tokens_scored_total{arch=jamba2-3b}": 3 * 2048}
    got = harness.reader("bdml.mfu").read(_ctx(registry=tokens))
    cfg = harness.load("configs", "jamba2-3b")
    want = 100 * 3 * 2048 * jamba.flops_per_token(cfg, 2048) / (2 * 197e12)
    assert got == pytest.approx(want)


def test_score_ms_is_the_mean_span():
    from chipbench.tests.test_layer_readers import rec
    spans = [rec("ml/score", 0.0, 0.4), rec("ml/score", 0.5, 0.6),
             rec("ml/wave", 0.0, 1.2)]
    assert harness.reader("ml.score_ms").read(_ctx(spans)) == \
        pytest.approx(500.0)


@pytest.mark.parametrize("registry,model_type", [
    ({"repro_ml_tokens_scored_total{arch=qwen2-1.5b}": 2048}, "jamba"),
    ({"repro_ml_tokens_scored_total{arch=jamba2-3b}": 2048,
      "repro_ml_tokens_scored_total{arch=qwen2-1.5b}": 2048}, "jamba"),
    ({"repro_ml_tokens_scored_total{arch=jamba2-3b}": 2048}, "qwen2")])
def test_mfu_is_silent_on_tokens_it_cannot_count(registry, model_type):
    """Tokens of another arch than the configuration's, or a model
    family with no FLOP count, give no reading rather than a wrong one."""
    cfg = dict(harness.load("configs", "jamba2-3b"), model_type=model_type)
    ctx = _ctx(registry=registry, config=cfg)
    assert harness.reader("bdml.mfu").read(ctx) is None


@pytest.mark.parametrize("metric", ["bdml.mfu", "ml.score_ms",
                                    "device.idle.bdml"])
def test_bdml_readers_are_silent_on_a_program_without_them(metric):
    ctx = _ctx(registry={"repro_ml_windows_scored_total": 3})
    assert harness.reader(metric).read(ctx) is None
