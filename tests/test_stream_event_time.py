"""Event-time streaming tests (arXiv:1609.07548: S-Store as the
polystore's time-ordered engine): bounded out-of-order ingest through
insertion buffers, per-stream low watermarks (min across shards),
``ewindow`` views closed only when the watermark passes, cross-stream
interval ``join`` — including the acceptance criterion that a join of
two sharded, out-of-order streams is bit-identical to the same join on
the unsharded, pre-sorted inputs — watermark-gated standing queries with
per-query late-row accounting, and the Planner's join home-engine pin.
"""
import numpy as np
import pytest

from repro.core import admin, bql
from repro.core.api import default_deployment
from repro.stream import shim
from repro.stream.engine import Stream, StreamException


def _jittered(ts, rng, jitter):
    """Arrival order of event times under bounded network jitter."""
    return np.argsort(ts + rng.uniform(-jitter, jitter, ts.shape[0]))


# -- out-of-order ingest ------------------------------------------------------
def test_plain_stream_append_counts_unchanged():
    """Streams without ts_field keep the exact PR-3 seq semantics —
    including the append result schema (no event-time keys)."""
    s = Stream("s", ("x",), capacity=8)
    assert s.append({"x": [1.0, 2.0]}) == {"appended": 2, "dropped": 0,
                                           "rows": 2}
    assert s.append({"x": []}) == {"appended": 0, "dropped": 0, "rows": 2}
    assert s.ts_field is None and s._pending_rows == 0
    with pytest.raises(StreamException):
        s.flush()                          # no event-time field
    with pytest.raises(StreamException):
        s.ewindow(4.0)


def test_out_of_order_rows_flush_in_ts_order():
    s = Stream("s", ("ts", "x"), capacity=64, ts_field="ts",
               max_delay=3.0)
    r = s.append({"ts": [5.0, 2.0, 7.0, 1.0], "x": [50, 20, 70, 10]})
    # watermark = 7 - 3 = 4: ts 1,2 flushed in order; 5,7 still pending
    assert r == {"appended": 4, "dropped": 0, "late": 0, "flushed": 2,
                 "pending": 2, "rows": 2}
    assert s.watermark == 4.0
    snap = s.snapshot()
    np.testing.assert_array_equal(np.asarray(snap.columns["ts"]), [1, 2])
    np.testing.assert_array_equal(np.asarray(snap.columns["seq"]),
                                  [0, 1])                # seq at flush
    s.append({"ts": [12.0], "x": [120]})   # wm -> 9: 5,7 flush
    np.testing.assert_array_equal(
        np.asarray(s.snapshot().columns["ts"]), [1, 2, 5, 7])


def test_equal_timestamps_keep_arrival_order():
    s = Stream("s", ("ts", "x"), capacity=64, ts_field="ts",
               max_delay=0.0)
    s.append({"ts": [3.0, 3.0, 3.0], "x": [1.0, 2.0, 3.0]})
    np.testing.assert_array_equal(
        np.asarray(s.snapshot().columns["x"]), [1, 2, 3])


def test_late_rows_dropped_and_counted():
    s = Stream("s", ("ts", "x"), capacity=64, ts_field="ts",
               max_delay=2.0)
    s.append({"ts": [10.0], "x": [1.0]})          # wm = 8
    r = s.append({"ts": [5.0, 9.0], "x": [2.0, 3.0]})   # 5 < 8: late
    assert r["late"] == 1 and r["appended"] == 1
    assert s.total_late == 1
    s.flush()
    np.testing.assert_array_equal(
        np.asarray(s.snapshot().columns["ts"]), [9, 10])


def test_flush_punctuation_closes_the_tail():
    s = Stream("s", ("ts", "x"), capacity=64, ts_field="ts",
               max_delay=100.0)
    s.append({"ts": np.arange(8, dtype=float), "x": np.zeros(8)})
    assert s.num_rows == 0                 # all pending: wm = 7 - 100
    out = s.flush()                        # punctuation: wm -> max ts
    assert out["flushed"] == 8 and out["watermark"] == 7.0
    assert s.num_rows == 8
    with pytest.raises(StreamException):
        Stream("p", ("x",), capacity=4).flush()


def test_seq_windows_still_work_on_event_time_streams():
    """seq is assigned at flush in ts order, so the seq-aligned ops keep
    working — and coincide with event order."""
    s = Stream("s", ("ts", "x"), capacity=64, ts_field="ts",
               max_delay=0.0)
    rng = np.random.default_rng(0)
    ts = np.arange(16, dtype=float)
    order = _jittered(ts, rng, 0.0)        # in order, delay 0
    s.append({"ts": ts[order], "x": (ts * 2)[order]})
    w = s.window(8)                        # seq window [8, 16)
    np.testing.assert_array_equal(np.asarray(w.attrs["ts"]),
                                  np.arange(8, 16))
    assert s.window_aggregate(8, "avg", "x") == pytest.approx(23.0)


# -- ewindow ------------------------------------------------------------------
def test_ewindow_closed_only_when_watermark_passes():
    s = Stream("s", ("ts", "x"), capacity=64, ts_field="ts",
               max_delay=2.0)
    with pytest.raises(StreamException):
        s.ewindow(4.0)                     # watermark not started
    s.append({"ts": [0.0, 1.0, 3.0], "x": [0, 1, 3]})   # wm = 1
    with pytest.raises(StreamException):
        s.ewindow(4.0)                     # [0,4) not closed at wm=1
    s.append({"ts": [6.5], "x": [65]})     # wm = 4.5: [0,4) closes
    w = s.ewindow(4.0)
    np.testing.assert_array_equal(np.asarray(w.attrs["ts"]), [0, 1, 3])
    assert w.dim_names == ("tick",)
    s.append({"ts": [10.5], "x": [105]})   # wm = 8.5: latest = [4,8)
    np.testing.assert_array_equal(
        np.asarray(s.ewindow(4.0).attrs["ts"]), [6.5])
    # slide alignment: latest [k*2, k*2+4) with end <= 8.5 is [4,8)
    np.testing.assert_array_equal(
        np.asarray(s.ewindow(4.0, 2.0).attrs["ts"]), [6.5])


def test_ewindow_may_be_empty_and_row_count_varies():
    """Event-time windows have density-dependent row counts; an empty
    closed window is legitimate (no readings in that span)."""
    s = Stream("s", ("ts", "x"), capacity=64, ts_field="ts",
               max_delay=0.0)
    s.append({"ts": [1.0, 2.0, 9.0], "x": [1, 2, 9]})   # wm = 9
    assert np.asarray(s.ewindow(4.0).attrs["ts"]).shape[0] == 0  # [4,8)
    np.testing.assert_array_equal(
        np.asarray(s.ewindow(8.0).attrs["ts"]), [1, 2])


def test_ewindow_evicted_window_raises():
    s = Stream("s", ("ts", "x"), capacity=6, ts_field="ts",
               max_delay=0.0)
    s.append({"ts": np.arange(8, dtype=float), "x": np.zeros(8)})
    # ring kept ts 2..7; the latest closed window [0,4) lost ts 0,1 to
    # eviction — no silent partials
    with pytest.raises(StreamException):
        s.ewindow(4.0)
    s.append({"ts": [11.0], "x": [0.0]})   # wm=11: latest closed = [4,8)
    np.testing.assert_array_equal(
        np.asarray(s.ewindow(4.0).attrs["ts"]), [4, 5, 6, 7])


# -- sharded event time -------------------------------------------------------
def _mk_sharded(name, fields, shards, capacity=256, shard_key=None,
                block_rows=4, ts_field="ts", max_delay=3.0):
    return Stream(name, fields, -(-capacity // shards),
                  shards=[f"streamstore{i}" for i in range(shards)],
                  shard_key=shard_key, block_rows=block_rows,
                  ts_field=ts_field, max_delay=max_delay)


def test_sharded_out_of_order_gather_bit_identical_to_unsharded():
    ref = Stream("s", ("ts", "x"), capacity=256, ts_field="ts",
                 max_delay=3.0)
    sh = _mk_sharded("s", ("ts", "x"), shards=3)
    rng = np.random.default_rng(1)
    ts = np.arange(96, dtype=float)
    order = _jittered(ts, rng, 1.4)
    for a in range(0, 96, 16):
        sl = order[a:a + 16]
        batch = {"ts": ts[sl], "x": np.sin(ts[sl])}
        ref.append(dict(batch))
        sh.append(dict(batch))
    ref.flush()
    sh.flush()
    for view in (lambda s: s.snapshot().columns["ts"],
                 lambda s: s.snapshot().columns["x"],
                 lambda s: s.snapshot().columns["seq"],
                 lambda s: s.ewindow(16.0).attrs["x"],
                 lambda s: s.window(32).attrs["x"]):
        np.testing.assert_array_equal(np.asarray(view(ref)),
                                      np.asarray(view(sh)))


def test_sharded_watermark_is_min_across_keyed_shards():
    sh = _mk_sharded("kh", ("ts", "k"), shards=2, shard_key="k",
                     max_delay=0.0)
    # key 0 -> shard 0 (max ts 10), key 1 -> shard 1 (max ts 2)
    sh.append({"ts": [10.0, 2.0], "k": [0.0, 1.0]})
    assert sh.watermark == 2.0             # min across shards with data
    st = sh.stats()
    assert st["shard_watermarks"] == {0: 10.0, 1: 2.0}
    assert st["watermark"] == 2.0 and st["pending"] == 1
    sh.append({"ts": [11.0], "k": [1.0]})  # the lagging shard catches up
    assert sh.watermark == 10.0
    # a never-seen shard must not hold the watermark at -inf forever:
    sh2 = _mk_sharded("kh2", ("ts", "k"), shards=2, shard_key="k",
                      max_delay=0.0)
    sh2.append({"ts": [5.0, 6.0], "k": [0.0, 2.0]})   # both hash shard 0
    assert sh2.watermark == 6.0


# -- cross-stream interval join ----------------------------------------------
def _feed_pair(bd, rng, *, shards_a, shards_b, rows=96, jitter=1.8,
               max_delay=6.0, presorted=False):
    """Two event-time streams over one deployment: jittered out-of-order
    delivery, or the pre-sorted in-order reference."""
    a = bd.register_stream("streamstore0", "j.abp", ("ts", "abp"),
                           capacity=4 * rows, shards=shards_a,
                           ts_field="ts", max_delay=max_delay)
    b = bd.register_stream("streamstore0", "j.ecg", ("ts", "ecg"),
                           capacity=4 * rows, shards=shards_b,
                           ts_field="ts", max_delay=max_delay)
    ts = np.arange(rows, dtype=float)
    va = 90.0 + np.sin(ts)
    tb = ts + 0.25
    vb = np.cos(ts)
    oa = np.arange(rows) if presorted else _jittered(ts, rng, jitter)
    ob = np.arange(rows) if presorted else _jittered(tb, rng, jitter)
    for s in range(0, rows, 16):
        a.append({"ts": ts[oa][s:s + 16], "abp": va[oa][s:s + 16]})
        b.append({"ts": tb[ob][s:s + 16], "ecg": vb[ob][s:s + 16]})
    a.flush()
    b.flush()
    return a, b


JOIN_Q = ("bdstream(join(ewindow(j.abp, 24), ewindow(j.ecg, 24),"
          " on=ts, tol=0.5))")


def test_join_bit_identical_sharded_out_of_order_vs_unsharded_presorted():
    """The acceptance criterion: joining two sharded streams fed out of
    order is bit-identical to the same join computed on the unsharded,
    pre-sorted inputs."""
    bd_ref = default_deployment()
    _feed_pair(bd_ref, np.random.default_rng(2), shards_a=1, shards_b=1,
               presorted=True)
    bd_sh = default_deployment()
    _feed_pair(bd_sh, np.random.default_rng(3), shards_a=3, shards_b=2)
    ref = bd_ref.query(JOIN_Q).value
    cur = bd_sh.query(JOIN_Q).value
    assert sorted(cur.columns) == sorted(ref.columns)
    assert len(np.asarray(cur.columns["dt"])) > 0
    for col in ref.columns:
        np.testing.assert_array_equal(np.asarray(ref.columns[col]),
                                      np.asarray(cur.columns[col]))
    assert bd_sh.engines["streamstore0"].get("j.abp").total_late == 0


def test_interval_join_tol_semantics():
    left = shim.dm.ArrayObject({"ts": shim.jnp.asarray([0.0, 5.0]),
                                "a": shim.jnp.asarray([1.0, 2.0])},
                               ("tick",))
    right = shim.dm.ArrayObject({"ts": shim.jnp.asarray([0.5, 4.0, 9.0]),
                                 "b": shim.jnp.asarray([10., 20., 30.])},
                                ("tick",))
    out = shim.interval_join(left, right, on="ts", tol=1.0)
    # |0-0.5|<=1 and |5-4|<=1 (inclusive bound); nothing matches 9
    np.testing.assert_array_equal(np.asarray(out.columns["l_a"]), [1, 2])
    np.testing.assert_array_equal(np.asarray(out.columns["r_b"]),
                                  [10, 20])
    np.testing.assert_array_equal(np.asarray(out.columns["dt"]),
                                  [0.5, -1.0])
    empty = shim.interval_join(left, right, on="ts", tol=0.1)
    assert np.asarray(empty.columns["dt"]).shape[0] == 0
    with pytest.raises(StreamException):
        shim.interval_join(left, right, on="nope")
    with pytest.raises(StreamException):
        shim.interval_join(left, right, tol=-1.0)


def test_colocated_partial_join_identical_and_counted():
    """Co-located sharded operands take the banded partial path; the
    result is bit-identical to the single-band join."""
    bd = default_deployment()
    _feed_pair(bd, np.random.default_rng(4), shards_a=2, shards_b=2)
    before = dict(shim.JOIN_STATS)
    via_bql = bd.query(JOIN_Q).value
    assert shim.JOIN_STATS["partial_joins"] == before["partial_joins"] + 1
    a = bd.engines["streamstore0"].get("j.abp")
    b = bd.engines["streamstore0"].get("j.ecg")
    assert a.shard_engines() == b.shard_engines()
    full = shim.interval_join(a.ewindow(24.0), b.ewindow(24.0),
                              on="ts", tol=0.5, bands=1)
    for col in full.columns:
        np.testing.assert_array_equal(np.asarray(full.columns[col]),
                                      np.asarray(via_bql.columns[col]))


def test_join_rides_staged_cast_to_relational():
    bd = default_deployment()
    _feed_pair(bd, np.random.default_rng(5), shards_a=2, shards_b=1)
    r = bd.query("bdrel(select l_ts, r_ecg from bdcast(" + JOIN_Q[:-1]
                 + "), j_tbl, '', relational) where l_ts >= 40)")
    lts = np.asarray(r.value.columns["l_ts"])
    assert lts.shape[0] > 0 and (lts >= 40).all()


def test_join_of_seq_windows_and_snapshots():
    """join accepts any window views — on= picks the shared field."""
    bd = default_deployment()
    s1 = bd.register_stream("streamstore0", "a.stream", ("t", "x"),
                            capacity=64)
    s2 = bd.register_stream("streamstore0", "b.stream", ("t", "y"),
                            capacity=64)
    s1.append({"t": np.arange(8, dtype=float), "x": np.zeros(8)})
    s2.append({"t": np.arange(8, dtype=float) + 0.25, "y": np.ones(8)})
    r = bd.query("bdstream(join(window(a.stream, 8),"
                 " snapshot(b.stream), on=t, tol=0.3))")
    assert np.asarray(r.value.columns["dt"]).shape[0] == 8


# -- standing queries: watermark gating + late accounting ---------------------
def test_standing_join_ticks_only_on_watermark_advance():
    bd = default_deployment()
    a, b = _feed_pair(bd, np.random.default_rng(6), shards_a=2,
                      shards_b=2)
    cq = bd.register_continuous(JOIN_Q, name="j")
    snap = bd.register_continuous("bdstream(snapshot(j.abp))",
                                  name="plain_snap")
    bd.streams.tick()
    assert cq.executions == 1 and cq.event_time
    for _ in range(3):                     # watermark unchanged: skipped
        bd.streams.tick()
    assert cq.executions == 1 and cq.wm_skips == 3
    assert snap.executions == 4            # non-event-time: every tick
    a.append({"ts": [200.0], "abp": [1.0]})    # watermark advances
    b.append({"ts": [200.0], "ecg": [1.0]})
    bd.streams.tick()
    assert cq.executions == 2 and cq.wm_skips == 3
    m = bd.streams.status()["queries"]["j"]
    assert m["wm_skips"] == 3 and m["event_time"] is True


def test_standing_join_reruns_when_only_one_side_advances():
    """A join must re-execute when ANY referenced stream's watermark
    moves — one side's window can close while the other side stalls
    (gating on the min watermark would serve stale results)."""
    bd = default_deployment()
    a, b = _feed_pair(bd, np.random.default_rng(11), shards_a=1,
                      shards_b=1, rows=32)
    cq = bd.register_continuous(JOIN_Q, name="j")
    bd.streams.tick()
    assert cq.executions == 1
    stale = np.asarray(cq.last_value.columns["dt"]).shape[0]
    a.append({"ts": [200.0], "abp": [1.0]})    # only the LEFT advances
    bd.streams.tick()
    assert cq.executions == 2 and cq.wm_skips == 0
    # the left ewindow moved on to [168,192): the answer really changed
    assert np.asarray(cq.last_value.columns["dt"]).shape[0] != stale
    bd.streams.tick()                          # nothing advanced: skip
    assert cq.executions == 2 and cq.wm_skips == 1


def test_late_rows_charged_only_to_queries_reading_that_stream():
    bd = default_deployment()
    lossy = bd.register_stream("streamstore0", "lossy.ts", ("ts", "x"),
                               capacity=64, ts_field="ts", max_delay=0.0)
    bd.register_stream("streamstore0", "stable.ts", ("ts", "x"),
                       capacity=64, ts_field="ts", max_delay=0.0)
    on_lossy = bd.register_continuous(
        "bdstream(ewindow(lossy.ts, 4))", name="on_lossy")
    on_stable = bd.register_continuous(
        "bdstream(ewindow(stable.ts, 4))", name="on_stable")
    lossy.append({"ts": [10.0], "x": [1.0]})
    lossy.append({"ts": [3.0, 4.0], "x": [2.0, 3.0]})   # both late
    lossy.append({"ts": [15.0], "x": [4.0]})   # closes [8,12)
    bd.engines["streamstore0"].get("stable.ts").append(
        {"ts": [10.0, 15.0], "x": [0.0, 1.0]})
    bd.streams.tick()
    assert on_lossy.late_seen == 2
    assert on_stable.late_seen == 0
    assert bd.monitor.stream_stats["on_lossy"]["late"] == 2


def test_watermark_surfaced_in_monitor_and_status():
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "wm.ts", ("ts", "x"),
                           capacity=64, ts_field="ts", max_delay=2.0)
    s.append({"ts": [0.0, 7.0], "x": [0.0, 1.0]})
    bd.streams.tick()
    st = admin.status(bd)
    info = st["streams"]["streams"]["wm.ts"]
    assert info["watermark"] == 5.0 and info["ts_field"] == "ts"
    assert info["pending"] == 1 and info["late"] == 0
    assert st["streams"]["watermarks"]["wm.ts"]["watermark"] == 5.0
    r = bd.query("bdstream(watermark(wm.ts))")
    assert float(r.value.columns["watermark"][0]) == 5.0
    # flush through BQL (punctuation as an island op)
    bd.query("bdstream(flush(wm.ts))")
    assert s.watermark == 7.0
    with pytest.raises(Exception):
        bd.query("bdstream(watermark(nope.ts))")


# -- planner ------------------------------------------------------------------
def test_planner_pins_join_reads_to_both_home_engines():
    bd = default_deployment()
    a = bd.register_stream("streamstore0", "p.a", ("ts", "x"),
                           capacity=256, shards=4, num_engines=4,
                           ts_field="ts", max_delay=0.0)
    b = bd.register_stream("streamstore0", "p.b", ("ts", "y"),
                           capacity=256, shards=4, num_engines=4,
                           ts_field="ts", max_delay=0.0)
    ts = np.arange(32, dtype=float)
    a.append({"ts": ts, "x": ts})
    b.append({"ts": ts, "y": ts})
    bd.rebalance_stream("p.b", shard=0, to_engine="streamstore1")
    assert a.home_engine == "streamstore0"
    assert b.home_engine == "streamstore1"
    q = ("bdstream(join(ewindow(p.a, 8), ewindow(p.b, 8),"
         " on=ts, tol=0.5))")
    plans = bd.planner.enumerate_plans(bql.parse(q))
    placed = {e for p in plans for e in p.node_engines.values()}
    assert placed == {"streamstore0", "streamstore1"}
    assert len(plans) == 2                 # not one per StreamEngine
    r = bd.query(q)                        # and the pinned plan runs
    assert np.asarray(r.value.columns["dt"]).shape[0] > 0


# -- live state & feeds -------------------------------------------------------
def test_export_state_roundtrip_preserves_event_time_state():
    s = Stream("m", ("ts", "x"), capacity=16, ts_field="ts",
               max_delay=10.0)
    s.append({"ts": [1.0, 8.0], "x": [1.0, 2.0]})       # all pending
    s.append({"ts": [0.5], "x": [3.0]})
    assert s._pending_rows == 3
    clone = Stream.from_state(s.export_state())
    assert clone.ts_field == "ts" and clone.max_delay == 10.0
    assert clone._pending_rows == 3 and clone.total_late == 0
    out = clone.flush()
    assert out["flushed"] == 3
    np.testing.assert_array_equal(
        np.asarray(clone.snapshot().columns["ts"]), [0.5, 1, 8])


def test_paired_mimic_feed_runs_standing_join_without_late_rows():
    from repro.data.mimic import stream_mimic_paired_waveforms
    bd = default_deployment()
    cq = bd.register_continuous(
        "bdstream(join(ewindow(mimic2v26.abp_stream, 16),"
        " ewindow(mimic2v26.ecg_stream, 16), on=ts, tol=0.5))",
        name="abp_ecg")
    infos = list(stream_mimic_paired_waveforms(
        bd, batch_rows=32, num_batches=8, jitter=2.0, max_delay=6.0))
    assert len(infos) == 9                 # 8 batches + final punctuation
    last = infos[-1]
    assert all(v == 0 for v in last["late"].values())   # bounded jitter
    assert cq.executions >= 2 and cq.errors == 0
    assert cq.cache_hits >= cq.executions - 1
    joined = cq.last_value
    assert np.asarray(joined.columns["dt"]).shape[0] > 0
    # the two jittered feeds reconstructed the exact in-order signal
    abp = bd.engines["streamstore0"].get("mimic2v26.abp_stream")
    snap = abp.snapshot()
    np.testing.assert_array_equal(np.asarray(snap.columns["ts"]),
                                  np.arange(8 * 32, dtype=float))


# -- idle-shard watermark timeout ---------------------------------------------
def _fake_clock(*streams):
    """Replace the streams' monotonic clock with a controllable one."""
    state = {"t": 1000.0}

    def now():
        return state["t"]

    for s in streams:
        s._now = now
    return state


def test_idle_shard_stalls_then_advances_after_timeout():
    """The ROADMAP idle-timeout: one quiet key range stalls the
    min-watermark (windows stay open) until ``idle_timeout`` elapses —
    then the idle shard is excluded and the watermark jumps without a
    manual flush()."""
    bd = default_deployment()
    sh = bd.register_stream(
        "streamstore0", "idle.stream", ("ts", "k"), capacity=1024,
        shards=2, num_engines=2, shard_key="k",
        ts_field="ts", max_delay=1.0, idle_timeout=5.0)
    clock = _fake_clock(sh)
    # both key ranges feed: k=0 -> shard 0, k=1 -> shard 1
    sh.append({"ts": [0.0, 1.0], "k": [0.0, 1.0]})
    sh.append({"ts": [2.0, 2.5], "k": [0.0, 1.0]})
    wm0 = sh.watermark
    assert wm0 == 1.0                       # min(2.0, 2.5) - 1.0
    # now only shard 0's range keeps feeding: the stream min stalls at
    # shard 1's last timestamp however far shard 0 advances
    for step in range(3):
        clock["t"] += 1.0
        sh.append({"ts": [10.0 + step], "k": [0.0]})
    assert sh.watermark == 1.5, "quiet shard should stall the min"
    # cross the idle threshold: the next arrival recomputes the basis
    # with shard 1 excluded and the watermark jumps to shard 0's frontier
    clock["t"] += 10.0
    sh.append({"ts": [13.0], "k": [0.0]})
    assert sh.watermark == 12.0             # 13.0 - max_delay
    # shard 1's range coming back re-enters the min (no longer idle);
    # below-watermark rows on it are late now — the punctuation cost
    out = sh.append({"ts": [5.0], "k": [1.0]})
    assert out["late"] == 1
    assert sh.watermark == 12.0
    sh.close()


def test_idle_advance_via_runtime_tick_without_any_arrivals():
    """A stream whose feeds ALL stop still advances: StreamRuntime.tick
    drives advance_idle_watermark(), so buffered rows flush and a
    watermark-gated standing query unsticks with no manual flush()."""
    bd = default_deployment()
    sh = bd.register_stream(
        "streamstore0", "idle.tick", ("ts", "k"), capacity=1024,
        shards=2, num_engines=2, shard_key="k",
        ts_field="ts", max_delay=2.0, idle_timeout=5.0)
    clock = _fake_clock(sh)
    cq = bd.register_continuous(
        "bdstream(aggregate(ewindow(idle.tick, 2), count(ts)))",
        name="idle_count")
    sh.append({"ts": [0.0, 1.0, 2.0, 3.0], "k": [0.0, 1.0, 0.0, 1.0]})
    bd.streams.tick()
    # watermark = min(shard maxes 2.0, 3.0) - max_delay = 0.0: the first
    # [0, 4) window is open, the standing query errors (no closed
    # ewindow yet) but the tick carries on
    assert sh.watermark == 0.0
    assert cq.executions + cq.errors >= 1
    # feeds stop; before the timeout a tick changes nothing...
    clock["t"] += 2.0
    bd.streams.tick()
    assert sh.watermark == 0.0 and sh._pending_rows > 0
    # ...after it, the tick itself flushes the stream out
    clock["t"] += 10.0
    ran = bd.streams.tick()
    assert sh.watermark == 3.0 and sh._pending_rows == 0
    # the SAME tick that advanced the idle watermark ran the gated
    # query successfully: ewindow [0, 2) is closed now and holds ts 0, 1
    assert [name for name, _ in ran] == ["idle_count"]
    assert float(np.asarray(
        cq.last_value.attrs["count_ts"])[0]) == 2.0
    sh.close()


def test_unsharded_idle_timeout_flushes_buffered_tail():
    """idle_timeout on a plain stream: after T seconds of silence the
    insertion buffer flushes in full (the automatic flush())."""
    s = Stream("idle.plain", ("ts",), capacity=64,
               ts_field="ts", max_delay=5.0, idle_timeout=3.0)
    clock = _fake_clock(s)
    s.append({"ts": [1.0, 4.0, 2.0]})
    assert s._pending_rows == 3             # watermark -1.0, nothing out
    clock["t"] += 1.0
    assert s.advance_idle_watermark()["flushed"] == 0   # not idle yet
    clock["t"] += 5.0
    out = s.advance_idle_watermark()
    assert out["flushed"] == 3 and s._pending_rows == 0
    assert s.watermark == 4.0
    np.testing.assert_array_equal(
        np.asarray(s.snapshot().columns["ts"]), [1.0, 2.0, 4.0])


def test_idle_timeout_ignored_without_event_time_axis():
    s = Stream("idle.plainest", ("v",), capacity=8, idle_timeout=1.0)
    s.append({"v": [1.0]})
    assert s.advance_idle_watermark() == {"flushed": 0, "dropped": 0}
