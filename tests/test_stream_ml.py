"""ml-island tests: ``bdml(infer(...))`` scores stream windows through
the model registry and the result is **bitwise** a direct
``registry.forward`` on the same rows — plain, sliding, sharded,
event-time and replayed-after-recovery streams all included — plus the
wave scheduler's one-wave-per-tick accounting, front-door scored
subscriptions ≡ direct standing queries, the jax-absent fallback, and
the admin/Monitor surface.  The CI jit-parity lane re-runs this file
under both REPRO_QUERY_BACKEND values: the inner window gather rides
the compiled stream path, so everything here must hold on both.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admin
from repro.core.api import default_deployment
from repro.models import registry
from repro.sharding import logical as L
from repro.stream import ml
from repro.stream.spec import Durability, EventTime, Sharding, StreamSpec

ARCH = "qwen2-1.5b"          # the "lm" alias; smallest forward in the pool
W = 16
# |island score - float32 reference score| in nats of a 64-token window
# on the reduced jamba2-3b: the island computes in bfloat16 (2**-8
# relative per rounding); over six seeds it reads 1.2e-5 to 8.2e-4, so
# the tolerance is 2.4x its largest reading.  The logits, not this mean,
# are what separate the float8 control (tests/test_jamba_ref.py).
SCORE_TOL = 2e-3


def direct_score(values, arch=ARCH, seed=0):
    """The reference the island must match bitwise: quantize the rows,
    run a plain eager ``registry.forward`` on the island's bfloat16
    weights, mean next-token NLL in f32."""
    cfg = registry.get_config(arch, reduced=True)
    params = L.init_params(jax.random.PRNGKey(seed), ml.weight_specs(cfg))
    toks = ml.quantize(np.asarray(values, np.float64), cfg.vocab_size)
    logits, _ = registry.forward(
        params, {"tokens": jnp.asarray(toks[None, :], jnp.int32)}, cfg,
        None)
    logp = jax.nn.log_softmax(logits[0, :-1].astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, jnp.asarray(toks[1:, None]),
                               -1)[..., 0]
    return nll.mean()


def _deploy(spec=None):
    bd = default_deployment()
    bd.register_model("lm")
    if spec is not None:
        bd.register_stream("streamstore0", spec)
    return bd


def _rows(n=W, seed=0):
    rng = np.random.default_rng(seed)
    return {"ts": np.arange(float(n)),
            "hr": 70 + 8 * np.sin(np.arange(n) / 3)
            + rng.standard_normal(n)}


# -- bit-identity: infer ≡ direct registry.forward ---------------------------
def test_infer_matches_direct_forward_bitwise():
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    rows = _rows()
    bd.engines["streamstore0"].get("vitals.hr").append(rows)
    out = bd.query(f"bdml(infer(window(vitals.hr, {W}), models.lm))").value
    assert out.columns["score"].dtype == jnp.float32
    assert int(out.columns["rows"][0]) == W
    want = direct_score(rows["hr"])
    err = float(jnp.abs(out.columns["score"][0] - want))
    assert err == 0.0, f"infer vs direct forward: {err:.3e}"


def test_infer_sliding_windows_each_match_direct():
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    rows = _rows(2 * W)
    bd.engines["streamstore0"].get("vitals.hr").append(rows)
    out = bd.query(
        f"bdml(infer(window(vitals.hr, {W}, {W}), models.lm))").value
    n = int(out.columns["window"].shape[0])
    assert n == 2
    for i in range(n):
        want = direct_score(rows["hr"][i * W:(i + 1) * W])
        err = float(jnp.abs(out.columns["score"][i] - want))
        assert err == 0.0, f"window {i}: {err:.3e}"


def test_infer_field_kwarg_and_defaults():
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    rows = _rows()
    bd.engines["streamstore0"].get("vitals.hr").append(rows)
    q = f"bdml(infer(window(vitals.hr, {W}), models.lm, field=%s))"
    explicit = bd.query(q % "hr").value
    default = bd.query(
        f"bdml(infer(window(vitals.hr, {W}), models.lm))").value
    # the default field skips the ts column and picks hr
    assert float(explicit.columns["score"][0]) == \
        float(default.columns["score"][0])
    ts_scored = bd.query(q % "ts").value
    want = direct_score(rows["ts"])
    assert float(jnp.abs(ts_scored.columns["score"][0] - want)) == 0.0


def test_sharded_scores_match_unsharded_bitwise():
    rows = _rows(2 * W, seed=3)
    plain = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    plain.engines["streamstore0"].get("vitals.hr").append(rows)
    sharded = _deploy(StreamSpec(
        "vitals.hr", ("ts", "hr"), capacity=64,
        sharding=Sharding(shards=2, num_engines=2)))
    sharded.engines["streamstore0"].get("vitals.hr").append(rows)
    q = f"bdml(infer(window(vitals.hr, {W}, {W}), models.lm))"
    a = plain.query(q).value
    b = sharded.query(q).value
    np.testing.assert_array_equal(np.asarray(a.columns["score"]),
                                  np.asarray(b.columns["score"]))


def test_event_time_window_scores_match_direct():
    bd = _deploy(StreamSpec(
        "icu.abp", ("ts", "abp"), capacity=128,
        event_time=EventTime("ts", max_delay=4.0)))
    s = bd.engines["streamstore0"].get("icu.abp")
    rng = np.random.default_rng(7)
    ts = np.arange(24.0)
    order = np.argsort(ts + rng.uniform(-2, 2, ts.shape[0]))
    s.append({"ts": ts[order], "abp": (80 + ts)[order]})
    s.flush()                              # close every window
    view = bd.query("bdstream(ewindow(icu.abp, 16.0))").value
    out = bd.query(
        "bdml(infer(ewindow(icu.abp, 16.0), models.lm))").value
    want = direct_score(np.asarray(view.attrs["abp"], np.float64))
    err = float(jnp.abs(out.columns["score"][0] - want))
    assert err == 0.0, f"event-time infer vs direct: {err:.3e}"
    # gathered window is event-time ordered regardless of arrival order
    np.testing.assert_array_equal(
        np.sort(np.asarray(view.attrs["ts"])), np.asarray(view.attrs["ts"]))


def test_replayed_durable_stream_scores_identically(tmp_path):
    spec = StreamSpec("vitals.hr", ("ts", "hr"), capacity=64,
                      durability=Durability(str(tmp_path / "wal"),
                                            checkpoint_every_rows=8))
    bd = _deploy(spec)
    stream = bd.engines["streamstore0"].get("vitals.hr")
    stream.append(_rows(seed=11))
    q = f"bdml(infer(window(vitals.hr, {W}), models.lm))"
    before = bd.query(q).value
    stream._durable.close()
    bd2 = default_deployment()             # the "restart"
    bd2.recover_stream("streamstore0", str(tmp_path / "wal"))
    bd2.register_model("lm")
    after = bd2.query(q).value
    np.testing.assert_array_equal(np.asarray(before.columns["score"]),
                                  np.asarray(after.columns["score"]))


# -- wave scheduling ----------------------------------------------------------
def test_standing_infer_queries_share_one_wave_per_tick():
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows())
    n = 3
    for i in range(n):
        bd.register_continuous(
            f"bdml(infer(window(vitals.hr, {W}), models.lm))"
            if i == 0 else
            f"bdml(infer(window(vitals.hr, {W}), models.lm, field=hr))",
            name=f"scored{i}")
    s0 = ml.stats()
    ran = bd.streams.tick()
    s1 = ml.stats()
    assert len(ran) == n
    assert s1["waves"] - s0["waves"] == 1
    assert s1["wave_submissions"] - s0["wave_submissions"] == n
    assert s1["infer_executions"] - s0["infer_executions"] == n
    bd.streams.tick()
    s2 = ml.stats()
    assert s2["waves"] - s1["waves"] == 1


def test_params_cache_shared_across_queries():
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows())
    q = f"bdml(infer(window(vitals.hr, {W}), models.lm))"
    s0 = ml.stats()
    bd.query(q)
    bd.query(q)
    s1 = ml.stats()
    # the (arch, seed, reduced) entry was loaded at most once this test;
    # the second execution is always a cache hit
    assert s1["params_cache_hits"] - s0["params_cache_hits"] >= 1
    assert ("qwen2-1.5b", 0, True) in ml._LOADED


# -- front door ---------------------------------------------------------------
def test_frontdoor_scored_subscription_matches_direct():
    from repro.serve.engine import ServeConfig
    from repro.serve.frontdoor import FrontDoor
    bd = _deploy()
    door = FrontDoor(bd, ServeConfig(streams=(
        StreamSpec("vitals.hr", ("ts", "hr"), capacity=64),)),
        stream_engine="streamstore0")
    q = f"bdml(infer(window(vitals.hr, {W}), models.lm))"
    sub_a = door.open_session("a").subscribe(q)
    sub_b = door.open_session("b").subscribe(q)
    direct = bd.register_continuous(q, name="direct")
    bd.engines["streamstore0"].get("vitals.hr").append(_rows(seed=5))
    bd.streams.tick()
    got_a, got_b = sub_a.poll(), sub_b.poll()
    assert len(got_a) == 1 and len(got_b) == 1
    sa = np.asarray(got_a[0][1].columns["score"])
    sb = np.asarray(got_b[0][1].columns["score"])
    sd = np.asarray(direct.last_value.columns["score"])
    np.testing.assert_array_equal(sa, sd)
    np.testing.assert_array_equal(sb, sd)
    # warm sharing: both tenants rode ONE shared standing query
    assert door.stats()["shared_queries"] == 1
    door.close()


def test_frontdoor_scored_bed_matches_the_jamba_reference():
    """A scored bed on the reduced jamba2-3b (the ``mamba`` alias): both
    tenants get the shared execution's score, the program's own forward
    rerun on the window rebuilds it bitwise (rerun mismatch 0), and it
    lies within the reference tolerance of the float32 reference."""
    from repro.models import jamba_ref
    from repro.serve.frontdoor import FrontDoor
    bd = default_deployment()
    bd.register_model("scorer", arch="mamba", seed=7)
    bd.register_stream("streamstore0", StreamSpec(
        "icu.bed0_abp", ("t", "abp"), capacity=128))
    door = FrontDoor(bd, stream_engine="streamstore0")
    q = "bdml(infer(window(icu.bed0_abp, 64), models.scorer, field=abp))"
    subs = [door.open_session(t).subscribe(q, every_n_ticks=2)
            for t in ("ward", "cardio")]
    rng = np.random.default_rng(3)
    abp = 90 + 12 * np.sin(np.arange(100) / 10) + rng.standard_normal(100)
    bd.engines["streamstore0"].get("icu.bed0_abp").append(
        {"t": np.arange(100.0), "abp": abp})
    assert bd.streams.tick() == [] and all(not s.poll() for s in subs)
    bd.streams.tick()
    got = [float(s.poll()[0][1].columns["score"][0]) for s in subs]
    assert got[0] == got[1] and door.stats()["shared_queries"] == 1
    loaded = ml.load_model("jamba2-3b", 7, True)
    # the window as the stream serves it (float32), which the island bins
    toks = ml.quantize(abp[:64].astype(np.float32), loaded.cfg.vocab_size)
    assert float(ml.score_tokens(loaded, toks)) == got[0]
    logits = jamba_ref.forward(loaded.params, toks, loaded.cfg)
    logp = jax.nn.log_softmax(logits[:-1], -1)
    want = float(-jnp.take_along_axis(logp, toks[1:, None], -1).mean())
    assert abs(got[0] - want) < SCORE_TOL, (got[0], want)
    door.close()


def test_ml_counters_and_spans():
    from repro.obs import metrics, trace
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows(2 * W))
    name = "repro_ml_tokens_scored_total"
    before = metrics.counter(name, arch=ARCH).value
    trace.reset()
    trace.set_enabled(True)
    try:
        bd.query(f"bdml(infer(window(vitals.hr, {W}, {W}), models.lm))")
    finally:
        trace.set_enabled(False)
    assert metrics.counter(name, arch=ARCH).value - before == 2 * W
    spans = [s for s in trace.spans() if s.name == "ml/score"]
    assert [(s.attrs["arch"], s.attrs["rows"]) for s in spans] == \
        [(ARCH, W), (ARCH, W)]
    loaded = ml.load_model(ARCH, 0, True)
    resident = sum(a.nbytes for a in jax.tree.leaves(loaded.params))
    assert {a.dtype for a in jax.tree.leaves(loaded.params)} == \
        {jnp.dtype(jnp.bfloat16)}
    assert metrics.gauge("repro_ml_param_bytes", arch=ARCH, seed=0,
                         reduced=True).value == resident


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_scan_path_counters_follow_the_dispatch_rule(platform, monkeypatch):
    """Each scored window adds its Mamba layers (6 in the reduced
    jamba2-3b) to the counter of the path ``uses_kernel`` gives for the
    weights' platform and the window's length; a model without Mamba
    layers adds to neither."""
    from repro.kernels.mamba_scan import mamba_scan as k
    from repro.obs import metrics
    rows = k.CHUNK
    bd = default_deployment()
    bd.register_model("mamba")
    bd.register_model("lm")
    bd.register_stream("streamstore0", StreamSpec("vitals.hr", ("ts", "hr"),
                                                  capacity=2 * rows))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows(2 * rows))
    monkeypatch.setattr(ml, "interpret_mode", lambda x: platform != "tpu")
    names = ("repro_ml_scan_kernel_total", "repro_ml_scan_reference_total")
    before = [metrics.counter(n).value for n in names]
    bd.query(f"bdml(infer(window(vitals.hr, {rows}, {rows}), models.mamba))")
    bd.query(f"bdml(infer(window(vitals.hr, {rows}, {rows}), models.lm))")
    moved = [metrics.counter(n).value - b for n, b in zip(names, before)]
    assert moved == ([2 * 6, 0] if platform == "tpu" else [0, 2 * 6])


# -- failure modes ------------------------------------------------------------
def test_incomplete_window_is_transient():
    from repro.core.executor import (DataUnavailableException,
                                     LocalQueryExecutionException)
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows(n=4))
    with pytest.raises(LocalQueryExecutionException) as exc:
        bd.query(f"bdml(infer(window(vitals.hr, {W}), models.lm))")
    # the cause chain carries the transient marker (plan-cache survival)
    assert isinstance(exc.value.__cause__, DataUnavailableException)
    # standing queries survive it: the error is isolated per tick
    cq = bd.register_continuous(
        f"bdml(infer(window(vitals.hr, {W}), models.lm))", name="scored")
    bd.streams.tick()
    assert cq.errors == 1 and cq.executions == 0


def test_jax_absent_is_graceful(monkeypatch):
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows())
    cq = bd.register_continuous(
        f"bdml(infer(window(vitals.hr, {W}), models.lm))", name="scored")
    monkeypatch.setattr(ml, "JAX_AVAILABLE", False)
    s0 = ml.stats()
    with pytest.raises(Exception, match="jax"):
        bd.query(f"bdml(infer(window(vitals.hr, {W}), models.lm))")
    ran = bd.streams.tick()                # the tick itself survives
    assert ran == []
    assert cq.errors == 1 and "jax" in cq.last_error
    assert ml.stats()["fallbacks"] - s0["fallbacks"] == 2
    monkeypatch.setattr(ml, "JAX_AVAILABLE", True)
    bd.streams.tick()
    assert cq.executions == 1              # recovered on the next tick


def test_unknown_model_and_bad_args():
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows())
    with pytest.raises(Exception, match="not registered"):
        bd.query(f"bdml(infer(window(vitals.hr, {W}), models.nope))")
    with pytest.raises(Exception, match="no field"):
        bd.query(f"bdml(infer(window(vitals.hr, {W}), models.lm,"
                 f" field=bogus))")
    with pytest.raises(ml.MLException, match="unknown model"):
        ml.resolve_arch("not-an-arch")
    assert ml.resolve_arch("moe") == "olmoe-1b-7b"
    assert ml.resolve_arch("qwen2-1.5b") == "qwen2-1.5b"


# -- surface ------------------------------------------------------------------
def test_admin_status_and_planner_pinning():
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows())
    resp = bd.query(f"bdml(infer(window(vitals.hr, {W}), models.lm))")
    # the ml branch pins the read to the model's home engine: one plan
    assert resp.plans_considered == 1
    assert "mlhost0" in resp.qep_id
    bd.streams.tick()
    st = admin.status(bd)
    assert st["ml"]["jax_available"] is True
    for key in ("models_loaded", "waves", "windows_scored",
                "infer_executions", "fallbacks"):
        assert key in st["ml"], key
    assert "mlhost0" in st["islands"]["ml"]
    assert st["engines"]["mlhost0"]["kind"] == "mlserve"


def test_unload_model_frees_the_cache_entry():
    key = ("qwen2-1.5b", 11, True)
    first = ml.load_model(*key)
    assert ml.unload_model(*key) is first and key not in ml._LOADED
    assert ml.unload_model(*key) is None
    again = ml.load_model(*key)
    assert again is not first
    for a, b in zip(jax.tree.leaves(first.params),
                    jax.tree.leaves(again.params)):
        assert bool(jnp.array_equal(a, b))
    ml.unload_model(*key)


def test_register_model_serves_the_weights_it_is_handed():
    """``register_model(params=...)`` installs the given tree (held in
    bfloat16) under the handle's key in place of the seed's draw, and
    refuses a tree that is not the arch's."""
    cfg = registry.get_config(ARCH, reduced=True)
    specs = ml.weight_specs(cfg)
    given = jax.tree.map(lambda a: a.astype(jnp.float32),
                         L.init_params(jax.random.PRNGKey(99), specs))
    bd = _deploy(StreamSpec("vitals.hr", ("ts", "hr"), capacity=64))
    bd.register_model("lm", seed=12, params=given)
    loaded = ml.load_model(ARCH, 12, True)
    for a, b in zip(jax.tree.leaves(loaded.params), jax.tree.leaves(given)):
        assert a.dtype == jnp.bfloat16 and bool(jnp.array_equal(a, b))
    bd.engines["streamstore0"].get("vitals.hr").append(_rows())
    got = bd.query(f"bdml(infer(window(vitals.hr, {W}), models.lm))").value
    toks = ml.quantize(_rows()["hr"], cfg.vocab_size)
    assert float(got.columns["score"][0]) == \
        float(ml.score_tokens(loaded, toks))
    ml.unload_model(ARCH, 12, True)
    wrong = dict(given, final_norm={"scale": jnp.ones((3,))})
    with pytest.raises(ml.MLException, match="wrong shape"):
        bd.register_model("lm", seed=13, params=wrong)
    with pytest.raises(ml.MLException, match="param tree"):
        bd.register_model("lm", seed=13, params={"embed": given["embed"]})
    assert (ARCH, 13, True) not in ml._LOADED
