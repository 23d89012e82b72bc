"""The harness's own arithmetic and rules, on the CPU: the trace
reduction, the peaks table, the names in
BENCHMARK.json, and the refusal to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import harness, traces

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_union_merges_overlaps():
    assert traces.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                             (5, 9)]


def test_reduce_events_busy_gaps_and_top_ops():
    ms = 1_000_000
    device = {"/device:TPU:0": [("fusion.1", 0, 2 * ms),
                                ("fusion.1", 1 * ms, 2 * ms),
                                ("copy.3", 6 * ms, 1 * ms),
                                ("late", 12 * ms, 5 * ms)]}
    host = [("bench/tick", 3 * ms, 2 * ms), ("bench/poll", 7 * ms, 3 * ms)]
    out = traces.reduce_events(device, host, (0, 10 * ms))
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.004)      # [0,3) + [6,7)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert out["device_ops"][1] == ["copy.3", pytest.approx(0.001)]
    # idle [3,6) is covered by bench/tick for 2 ms, [7,10) by bench/poll
    assert sorted((n, round(s, 9)) for n, s in out["idle_gaps"]) == [
        ("bench/poll", 0.003), ("bench/tick", 0.003)]


def test_reduce_events_averages_devices_and_is_silent_without_ops():
    ms = 1_000_000
    two = {"/device:TPU:0": [("a", 0, 4 * ms)],
           "/device:TPU:1": [("a", 0, 2 * ms)]}
    assert traces.reduce_events(two, [], (0, 8 * ms))["busy_s"] == \
        pytest.approx(0.003)
    assert traces.reduce_events({}, [], (0, 8 * ms)) is None


def test_read_xplane_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with harness.annotate("window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    device, host = traces.read_xplane(str(tmp_path))
    assert [n for n, _, _ in host] == ["bench/window"]
    # the CPU has no device plane: the reduction reads nothing
    assert traces.reduce_events(device, host, (host[0][1],
                                host[0][1] + host[0][2])) is None


def test_readers_see_every_registry_series_and_their_own_counters():
    from repro.obs import metrics

    seen = {"n": 5}
    probe = harness.Probe(True, 0.0, harness.Compiles(),
                          {"m": lambda: dict(seen)})
    metrics.histogram("chipbench_test_seconds").observe(1.0)
    probe.before = probe._counters()
    metrics.histogram("chipbench_test_seconds").observe(0.5)
    metrics.counter("chipbench_test_total", kind="a").inc(3)
    seen["n"] = 12
    probe.after = probe._counters()
    probe.spans, probe.reduced, probe.traced = [], None, (0.0, 1.0)
    ctx = probe.context("c", {}, {}, "TPU v5 lite")
    assert ctx["registry"]["chipbench_test_seconds"] == (1, 0.5)
    assert ctx["registry"]["chipbench_test_total{kind=a}"] == 3
    assert ctx["own"] == {"m": {"n": 7}}


def test_peaks_table_refuses_unknown_kinds():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_benchmark_names_units_and_files():
    b = harness.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(harness.HERE / "metrics" / f"{m['name']}.py")
    for c in b["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(harness.ROOT /
                                                        c["file"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        traffic = harness.load("traffic", w["traffic"])
        harness.generator(traffic["generator"])
        assert harness.load("limits", w["name"])
        # every cell reports setup_s, another end-to-end metric and a
        # per-layer metric
        assert len(harness.e2e_names(b, w["name"])) >= 2
        assert harness.layer_names(b, w["name"])
    assert len(json.dumps(b)) < 64 * 1024


def test_entry_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "batch-array",
         "--seed", "1", "--seconds", "1"], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs 1 TPU" in out.stderr
