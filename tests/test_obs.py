"""Observability tests (repro.obs): span nesting and cross-thread
propagation (executor workers, committer lanes), histogram percentile
correctness against numpy, registry snapshot consistency under
concurrent writers, Chrome-trace export round-trip, the slow-op
threshold with an injected clock, Prometheus text exposition + the
/metrics HTTP endpoint, the late-row/eviction metric feeds, and the
admin.status() vs background-mutation race regression."""
import json
import re
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import admin
from repro.core.api import default_deployment
from repro.obs import metrics, trace

WINDOW_CQ = ("bdarray(aggregate(bdcast(bdstream(window("
             "mimic2v26.waveform_stream, 32)), w_arr,"
             " '<signal:double>[tick=0:31,32,0]', array), avg(signal)))")


@pytest.fixture
def traced():
    prev = trace.set_enabled(True)
    trace.reset()
    yield
    trace.set_enabled(prev)
    trace.reset()


@pytest.fixture
def registry():
    metrics.reset()
    yield metrics.REGISTRY
    metrics.reset()


# -- tracing core -------------------------------------------------------------
def test_span_nesting_links_parent_and_trace(traced):
    with trace.span("stream/tick", trace_id="tick-1") as root:
        with trace.span("planner/query") as child:
            with trace.span("executor/node", engine="e0"):
                pass
    recs = {r.name: r for r in trace.spans()}
    assert set(recs) == {"stream/tick", "planner/query", "executor/node"}
    assert recs["stream/tick"].parent_id is None
    assert recs["planner/query"].parent_id == root.span_id
    assert recs["executor/node"].parent_id == child.span_id
    assert {r.trace_id for r in recs.values()} == {"tick-1"}
    assert recs["executor/node"].attrs["engine"] == "e0"


def test_disabled_tracing_is_noop():
    prev = trace.set_enabled(False)
    try:
        trace.reset()
        assert trace.span("x/y") is trace.NOOP
        with trace.span("x/y") as sp:
            sp.set(a=1)                       # no-op surface
        assert trace.spans() == []

        def fn():
            return 7
        assert trace.bind(fn) is fn           # identity when disabled
    finally:
        trace.set_enabled(prev)


def test_top_level_spans_get_distinct_trace_ids(traced):
    with trace.span("a/one"):
        pass
    with trace.span("a/two"):
        pass
    ids = [r.trace_id for r in trace.spans()]
    assert len(set(ids)) == 2


def test_span_records_error_attr(traced):
    with pytest.raises(ValueError):
        with trace.span("executor/node"):
            raise ValueError("boom")
    (rec,) = trace.spans()
    assert rec.attrs["error"] == "ValueError"


def test_bind_propagates_parent_across_pool_threads(traced):
    def work(i):
        with trace.span("executor/task", i=i):
            time.sleep(0.001)
        return i

    with trace.span("executor/plan") as root:
        bound = trace.bind(work)
        with ThreadPoolExecutor(max_workers=4) as pool:
            # one bound fn running concurrently on several threads: each
            # call must plant/reset only its own contextvar token
            assert sorted(pool.map(bound, range(8))) == list(range(8))
    recs = [r for r in trace.spans() if r.name == "executor/task"]
    assert len(recs) == 8
    assert all(r.parent_id == root.span_id for r in recs)
    assert all(r.trace_id == root.trace_id for r in recs)
    main_tid = threading.get_ident()
    assert any(r.thread_id != main_tid for r in recs)


def test_chrome_trace_round_trip(traced, tmp_path):
    def work(i):
        with trace.span("committer/commit", shard=i):
            pass

    with trace.span("stream/append", trace_id="tick-3") as root:
        with ThreadPoolExecutor(max_workers=2) as pool:
            list(pool.map(trace.bind(work), range(2)))
    out = tmp_path / "trace.json"
    n = trace.save_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert n == len(xs) == 3
    for e in xs:
        assert e["dur"] >= 1 and isinstance(e["ts"], int)
        assert e["cat"] in ("stream", "committer")
        assert e["args"]["trace_id"] == "tick-3"
    # cross-thread children carry flow arrows: "s" on the parent thread,
    # "f" (bp="e") on the child's, sharing the child's span id
    starts = {e["id"]: e for e in events if e["ph"] == "s"}
    finishes = {e["id"]: e for e in events if e["ph"] == "f"}
    child_ids = {e["args"]["span_id"] for e in xs
                 if e["ph"] == "X" and e["name"] == "committer/commit"
                 and e["tid"] != root.span_id}
    cross = {e["args"]["span_id"] for e in xs
             if e["args"]["parent_id"] is not None
             and e["tid"] != next(x["tid"] for x in xs
                                  if x["name"] == "stream/append")}
    assert set(starts) == set(finishes) == cross and child_ids
    for sid in cross:
        assert finishes[sid]["bp"] == "e"
        assert starts[sid]["tid"] != finishes[sid]["tid"]
    # thread-name metadata for every participating thread
    tids = {e["tid"] for e in xs}
    named = {e["tid"] for e in events if e["ph"] == "M"}
    assert tids <= named


def test_flamegraph_shows_paths_and_counts(traced):
    with trace.span("stream/tick"):
        for _ in range(3):
            with trace.span("planner/query"):
                pass
    text = trace.flamegraph()
    assert "stream/tick" in text and "planner/query" in text
    row = next(ln for ln in text.splitlines() if "planner/query" in ln)
    assert re.search(r"\s3\s", row)           # call count aggregated


def _rec(name, span_id, parent_id, start, duration, thread_id=1):
    return trace.SpanRecord(name=name, trace_id="t", span_id=span_id,
                            parent_id=parent_id, start=start,
                            duration=duration, thread_id=thread_id,
                            thread_name=f"th{thread_id}")


def test_self_times_clip_and_merge_children_across_threads():
    recs = [_rec("stream/tick", 1, None, 0.0, 10.0),
            _rec("stream/query", 2, 1, 1.0, 3.0),
            _rec("planner/query", 3, 2, 2.0, 1.0),
            # a pool worker's child overlapping its sibling on another
            # thread: the overlap [3, 4) is not taken twice
            _rec("executor/node", 4, 1, 3.0, 3.0, thread_id=2),
            # a child outliving its parent is clipped to [8, 10)
            _rec("serve/fanout", 5, 1, 8.0, 4.0),
            _rec("stream/gather", 6, None, 20.0, 2.0)]
    own = trace.self_times(recs)
    # tick: 10 - |[1, 6) u [8, 10)| = 3; query: 3 - 1; leaves keep all
    assert own == pytest.approx({1: 3.0, 2: 2.0, 3: 1.0, 4: 3.0, 5: 4.0,
                                 6: 2.0})
    text = trace.flamegraph(recs)
    assert text.splitlines()[0].split()[:3] == ["total_ms", "self_ms",
                                                "calls"]
    row = next(ln for ln in text.splitlines() if "stream/tick" in ln)
    assert row.split()[:3] == ["10000.00", "3000.00", "1"]


@pytest.mark.parametrize("on", [True, False])
def test_program_spans_are_profiler_host_events(on, tmp_path):
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    names = ("stream/tick", "stream/gather", "planner/query",
             "executor/node", "device/wait")
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    prev = trace.set_enabled(on)
    trace.reset()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with trace.span("stream/tick", tick=3):
            with trace.span("stream/gather", rows=32):
                pass
            with trace.span("planner/query"):
                with trace.span("executor/node"):
                    trace.device_wait(f(x))
        jax.profiler.stop_trace()
    finally:
        trace.set_enabled(prev)
        trace.reset()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        host[e.name] = e
    if not on:
        assert host == {}
        return
    assert set(host) == set(names)
    tick = host["stream/tick"]
    assert dict(tick.stats)["step_num"] == 3
    # one clock: every child lies inside the tick
    for e in host.values():
        assert tick.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= \
            tick.start_ns + tick.duration_ns


@pytest.mark.parametrize("on", [True, False])
def test_device_wait_spans_only_under_tracing(on):
    import jax.numpy as jnp

    from repro.core import datamodel as dm

    prev = trace.set_enabled(on)
    trace.reset()
    try:
        value = dm.ArrayObject({"a": jnp.arange(4.0) * 2}, ("tick",))
        assert trace.device_wait(value) is value
        arr = jnp.ones(3) + 1
        assert trace.device_wait(arr) is arr
        waits = [r for r in trace.spans() if r.name == "device/wait"]
    finally:
        trace.set_enabled(prev)
        trace.reset()
    assert len(waits) == (2 if on else 0)


@pytest.mark.parametrize("shards,backend,path", [
    (2, "interpreter", "interpreted"), (1, "jit", "compiled")])
def test_window_tick_records_gather_and_h2d_bytes(traced, registry,
                                                  monkeypatch, shards,
                                                  backend, path):
    from repro.stream.spec import Sharding, StreamSpec

    monkeypatch.setenv("REPRO_QUERY_BACKEND", backend)
    cap, size, fields = 256, 32, ("hr", "bp")
    bd = default_deployment()
    stream = bd.register_stream("streamstore0", StreamSpec(
        "vitals.stream", fields, capacity=cap,
        sharding=Sharding(shards=shards) if shards > 1 else None))
    stream.append({f: np.arange(100.0) for f in fields})
    bd.register_continuous(f"bdstream(window(vitals.stream, {size}))",
                           every_n_ticks=1, name="w")
    trace.reset()
    before = metrics.counter("repro_stream_h2d_bytes_total").value
    bd.streams.tick()
    moved = metrics.counter("repro_stream_h2d_bytes_total").value - before
    gathers = [r for r in trace.spans() if r.name == "stream/gather"]
    assert [g.attrs["path"] for g in gathers] == [path]
    # float32 window columns on the device; the compiled plan also
    # hands over the ring stacked oldest-first in float64
    window = size * len(fields) * 4
    if path == "interpreted":
        assert gathers[0].attrs["rows"] == size
        assert moved == window
    else:
        assert gathers[0].attrs["rows"] == 100
        assert moved == window + cap * len(fields) * 8
    tick = next(r for r in trace.spans() if r.name == "stream/tick")
    assert gathers[0].trace_id == tick.trace_id


def test_slow_op_threshold_with_injected_clock(traced, monkeypatch):
    ticks = iter([0.0, 0.050, 1.0, 1.250])    # 50 ms span, then 250 ms
    monkeypatch.setattr(trace, "_clock", lambda: next(ticks))
    monkeypatch.setenv("REPRO_SLOW_OP_MS", "100")
    monkeypatch.setenv("REPRO_TRACE", "1")
    trace.refresh()
    assert trace.slow_op_threshold_ms() == 100.0
    with trace.span("executor/cast", method="staged"):
        pass
    with trace.span("migrator/route", src="a", dst="b"):
        pass
    slow = trace.slow_ops()
    assert [s["name"] for s in slow] == ["migrator/route"]
    assert slow[0]["ms"] == 250.0
    assert slow[0]["attrs"] == {"src": "a", "dst": "b"}
    monkeypatch.delenv("REPRO_SLOW_OP_MS")
    monkeypatch.delenv("REPRO_TRACE")
    trace.refresh()                           # back to defaults


# -- metrics core -------------------------------------------------------------
def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-4.0, sigma=2.0, size=20_000)
    h = metrics.Histogram()
    for v in samples:
        h.observe(v)
    assert h.count == samples.size
    assert h.sum == pytest.approx(samples.sum())
    for q in (0.50, 0.95, 0.99):
        ref = float(np.quantile(samples, q))
        est = h.quantile(q)
        # log-bucket interpolation is within one bucket ratio of truth
        assert ref / metrics.BUCKET_RATIO <= est \
            <= ref * metrics.BUCKET_RATIO


def test_counter_set_total_is_monotone(registry):
    c = metrics.counter("repro_test_total", "t", stream="s")
    c.set_total(5)
    c.set_total(3)                            # stale source: ignored
    assert c.value == 5
    c.inc(2)
    assert c.value == 7
    # same labels -> same series object
    assert metrics.counter("repro_test_total", stream="s") is c


def test_metric_type_mismatch_raises(registry):
    metrics.counter("repro_test_kind_total")
    with pytest.raises(ValueError):
        metrics.gauge("repro_test_kind_total")


def test_registry_snapshot_consistent_under_concurrent_writers(registry):
    stop = threading.Event()
    n_threads, per_thread = 4, 2000

    def writer(tid):
        c = metrics.counter("repro_conc_total", "c", t=tid)
        h = metrics.histogram("repro_conc_seconds", "h")
        for i in range(per_thread):
            c.inc()
            h.observe(1e-4 * (i + 1))

    def reader():
        while not stop.is_set():
            snap = metrics.snapshot()
            json.dumps(snap)                  # JSON-safe at any moment
            metrics.prometheus_text()

    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers:
        t.start()
    with ThreadPoolExecutor(max_workers=n_threads) as pool:
        list(pool.map(writer, range(n_threads)))
    stop.set()
    for t in readers:
        t.join(timeout=5.0)
        assert not t.is_alive()
    snap = metrics.snapshot()
    totals = {r["labels"]["t"]: r["value"]
              for r in snap["repro_conc_total"]["series"]}
    assert totals == {str(i): per_thread for i in range(n_threads)}
    (hist,) = snap["repro_conc_seconds"]["series"]
    assert hist["count"] == n_threads * per_thread


def test_prometheus_text_format(registry):
    metrics.counter("repro_fmt_total", "a counter", stream="s\"1\"").inc(3)
    metrics.gauge("repro_fmt_gauge", "a gauge").set(1.5)
    h = metrics.histogram("repro_fmt_seconds", "a histogram")
    for v in (0.001, 0.01, 0.1):
        h.observe(v)
    text = metrics.prometheus_text()
    assert '# TYPE repro_fmt_total counter' in text
    assert 'repro_fmt_total{stream="s\\"1\\""} 3' in text
    assert "repro_fmt_gauge 1.5" in text
    # histogram: cumulative buckets ending in +Inf == _count, plus _sum
    buckets = [int(m.group(1)) for m in re.finditer(
        r'repro_fmt_seconds_bucket\{le="[^"]+"\} (\d+)', text)]
    assert buckets == sorted(buckets) and buckets[-1] == 3
    assert 'repro_fmt_seconds_bucket{le="+Inf"} 3' in text
    assert "repro_fmt_seconds_count 3" in text
    # every sample line parses as <name>{labels} <value>
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        assert re.match(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$',
                        line), line


def test_metrics_http_endpoint(registry):
    metrics.counter("repro_http_total", "served").inc()
    server = metrics.start_http_server(port=0)
    try:
        host, port = server.server_address[:2]
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5) as resp:
            body = resp.read().decode()
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
        assert "repro_http_total 1" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{host}:{port}/nope", timeout=5)
    finally:
        server.shutdown()


# -- integration: spans across the real layers --------------------------------
def test_tick_trace_spans_cross_layers_with_parent_links(traced):
    from repro.data.mimic import stream_mimic_waveforms
    bd = default_deployment()
    bd.register_continuous(WINDOW_CQ, every_n_ticks=1, name="wave_avg")
    for _ in stream_mimic_waveforms(bd, batch_rows=32, num_batches=3):
        pass
    recs = trace.spans()
    layers = {r.name.split("/", 1)[0] for r in recs}
    assert {"stream", "planner", "executor", "committer"} <= layers
    by_id = {r.span_id: r for r in recs}
    # every tick roots one trace: stream/query -> planner/query ->
    # executor/plan -> executor/node chain shares the tick's trace_id
    tick = next(r for r in recs if r.name == "stream/tick")
    assert tick.trace_id.startswith("tick-")
    q = next(r for r in recs if r.name == "stream/query")
    assert by_id[q.parent_id].name == "stream/tick"
    planner_spans = [r for r in recs if r.name == "planner/query"]
    assert any(r.parent_id is not None
               and by_id[r.parent_id].name == "stream/query"
               for r in planner_spans)
    nodes = [r for r in recs if r.name == "executor/node"]
    assert nodes and all(
        by_id[r.parent_id].name == "executor/plan" for r in nodes)
    # concurrent executor stages hop threads; parent links must survive
    plan = next(r for r in recs if r.name == "executor/plan")
    assert any(r.thread_id != plan.thread_id for r in nodes)


def test_sharded_append_spans_reach_committer_lanes(traced):
    bd = default_deployment()
    bd.register_stream("streamstore0", "vitals.stream", ("hr",),
                       capacity=8192, shards=2, num_engines=2)
    stream = bd.engines["streamstore0"].get("vitals.stream")
    try:
        # >= PARALLEL_APPEND_MIN_ROWS rows: commits fan out to the
        # scatter pool, so the lane spans run on pool threads
        stream.append({"hr": np.arange(4096.0)})
    finally:
        stream.close()
    recs = trace.spans()
    root = next(r for r in recs if r.name == "stream/append")
    # lane spans carry shard=; the shard rings' own commit spans (from
    # Stream._append_prepared, nested inside) carry lane= instead
    commits = [r for r in recs if r.name == "committer/commit"
               and "shard" in r.attrs]
    assert {r.attrs["shard"] for r in commits} == {0, 1}
    assert all(r.parent_id == root.span_id for r in commits)
    assert all(r.trace_id == root.trace_id for r in commits)
    assert any(r.thread_id != root.thread_id for r in commits)
    stages = [r for r in recs if r.name == "stream/reserve"
              or r.name == "stream/stage"]
    assert {r.name for r in stages} == {"stream/reserve", "stream/stage"}


# -- metric feeds from the running system -------------------------------------
def test_late_and_eviction_metrics_exported(registry):
    bd = default_deployment()
    bd.register_stream("streamstore0", "ev.stream", ("ts", "x"),
                       capacity=4, ts_field="ts", max_delay=0.0)
    stream = bd.engines["streamstore0"].get("ev.stream")
    # 10 rows into a 4-slot ring: 6 evicted, eviction horizon advances
    stream.append({"ts": np.arange(10.0), "x": np.zeros(10)})
    r = stream.append({"ts": [2.0], "x": [0.0]})    # below wm: late
    assert r["late"] == 1
    bd.streams.tick()
    snap = metrics.snapshot()
    late = {r["labels"]["stream"]: r["value"] for r in
            snap["repro_stream_late_rows_dropped_total"]["series"]}
    assert late["ev.stream"] == 1
    ev = {r["labels"]["stream"]: r["value"] for r in
          snap["repro_stream_eviction_ts"]["series"]}
    assert ev["ev.stream"] == stream.evicted_ts > float("-inf")
    wm = {r["labels"]["stream"]: r["value"] for r in
          snap["repro_stream_watermark"]["series"]}
    assert wm["ev.stream"] == stream.watermark


def test_standing_query_counters_absorbed(registry):
    from repro.data.mimic import stream_mimic_waveforms
    bd = default_deployment()
    bd.register_continuous(WINDOW_CQ, every_n_ticks=1, name="wave_avg")
    for _ in stream_mimic_waveforms(bd, batch_rows=32, num_batches=3):
        pass
    snap = metrics.snapshot()
    ticks = {r["labels"]["query"]: r["value"] for r in
             snap["repro_stream_query_ticks_total"]["series"]}
    assert ticks["wave_avg"] == 3
    (tick_hist,) = snap["repro_stream_tick_seconds"]["series"]
    assert tick_hist["count"] == 3
    modes = {r["labels"]["mode"]: r["value"] for r in
             snap["repro_queries_total"]["series"]}
    assert modes.get("lean", 0) >= 3


# -- the status() race regression (satellite: snapshot under lock) ------------
def test_status_consistent_while_monitoring_task_mutates():
    """admin.status() used to iterate Monitor dicts the background
    MonitoringTask / tick driver mutate — hammer it against a running
    fleet and require structurally complete JSON-serializable output."""
    from repro.data.mimic import load_mimic_demo
    bd = default_deployment()
    load_mimic_demo(bd)
    bd.register_stream("streamstore0", "vitals.stream", ("hr",),
                       capacity=2048)
    bd.register_continuous(
        "bdstream(aggregate(window(vitals.stream, 32), avg(hr)))",
        every_n_ticks=1, name="hr_avg")
    stream = bd.engines["streamstore0"].get("vitals.stream")
    task = bd.start_monitoring(interval_seconds=0.001)
    task.start()
    stop = threading.Event()
    errors = []

    def producer():
        rng = np.random.default_rng(0)
        while not stop.is_set():
            try:
                with stream.producer() as p:
                    p.append({"hr": rng.standard_normal(64)})
                bd.streams.tick()
            except Exception as exc:          # noqa: BLE001 — recorded
                errors.append(exc)
                return

    threads = [threading.Thread(target=producer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            st = admin.status(bd)
            json.dumps(st)                    # serializable mid-mutation
            assert set(st) == {"engines", "islands", "monitor",
                               "concurrency", "streams", "plan_cache",
                               "catalog", "serve", "ml"}
            assert "watermarks" in st["streams"]
            json.loads(bd.monitor.to_json())
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5.0)
        task.stop()
        bd.monitoring_task = None
    assert errors == []
    assert all(not t.is_alive() for t in threads)
