"""Sharded streaming scale-out tests (arXiv:1609.07548 §streams across
engines): a stream over 1, 2 or 3 rings — round-robin or key-hashed —
reads bit-identically to the one-ring stream (snapshots, windows,
ewindows, aggregates from per-ring partials, state round trips), live
ring migration (Migrator ``stream`` route) preserving seq/drop
accounting mid-standing-query, the Monitor-driven rebalance hook, and
the opt-in background tick driver."""
import threading
import time

import numpy as np
import pytest

from repro.core import admin
from repro.core.api import default_deployment
from repro.core.migrator import MigrationException, MigrationParams
from repro.core.monitor import Monitor
from repro.stream.engine import Stream

# (rings, shard_key): one ring is the unsharded stream itself
RING_LAYOUTS = [(n, key) for n in (1, 2, 3) for key in (None, "x")]


def _mk(rings, capacity=96, fields=("x", "y"), shard_key=None,
        block_rows=4, **kwargs):
    """A stream named ``s`` holding ``capacity`` rows over ``rings``
    rings (placed on streamstore0..N-1 when there are several)."""
    placement = ([f"streamstore{i}" for i in range(rings)]
                 if rings > 1 else None)
    return Stream("s", fields, -(-capacity // rings), shards=placement,
                  shard_key=shard_key, block_rows=block_rows, **kwargs)


# -- every ring layout reads like the one-ring stream -------------------------
@pytest.mark.parametrize("rings,shard_key", RING_LAYOUTS)
def test_gather_bit_identical_to_unsharded(rings, shard_key):
    # each ring holds every row: no layout evicts
    ref = Stream("s", ("x", "y"), 96)
    sh = _mk(rings, capacity=96 * rings, shard_key=shard_key)
    rng = np.random.default_rng(0)
    for _ in range(6):
        batch = {"x": rng.integers(0, 9, 13).astype(float),
                 "y": rng.standard_normal(13)}
        ref.append(batch)
        sh.append(batch)
    views = (lambda s: s.snapshot().columns["y"],
             lambda s: s.snapshot().columns["seq"],
             lambda s: s.window(32).attrs["y"],        # tumbling
             lambda s: s.window(16, 8).attrs["y"])     # sliding
    # the export_state/from_state/clone copies read the same too
    copies = (sh, Stream.from_state(sh.export_state()), sh.clone("s2"))
    for copy in copies:
        assert copy.num_shards == rings
        assert copy.total_appended == ref.total_appended == 78
        for view in views:
            np.testing.assert_array_equal(np.asarray(view(ref)),
                                          np.asarray(view(copy)))
    # event time: the same jittered feed (no row late in any layout)
    # gives the same closed ewindows, tumbling and sliding
    eref = Stream("e", ("ts", "x"), 256, ts_field="ts", max_delay=3.0)
    esh = _mk(rings, capacity=256, fields=("ts", "x"),
              shard_key=shard_key, ts_field="ts", max_delay=3.0)
    ts = np.arange(96, dtype=float)
    order = np.argsort(ts + rng.uniform(-1.4, 1.4, 96))
    for a in range(0, 96, 16):
        batch = {"ts": ts[order[a:a + 16]], "x": ts[order[a:a + 16]] % 7}
        eref.append(dict(batch))
        esh.append(dict(batch))
    for s in (eref, esh):
        s.flush()
    assert eref.total_late == esh.total_late == 0
    for copy in (esh, esh.clone("e2")):
        for view in (lambda s: s.snapshot().columns["ts"],
                     lambda s: s.ewindow(8.0).attrs["x"],
                     lambda s: s.ewindow(8.0, 4.0).attrs["ts"]):
            np.testing.assert_array_equal(np.asarray(view(eref)),
                                          np.asarray(view(copy)))


@pytest.mark.parametrize("data", ["normal", "integer"])
@pytest.mark.parametrize("fn", ["count", "sum", "avg", "min", "max"])
@pytest.mark.parametrize("rings,shard_key", RING_LAYOUTS)
def test_window_aggregate_partials_match_unsharded(rings, shard_key, fn,
                                                   data):
    ref = Stream("s", ("x", "y"), 192)
    sh = _mk(rings, capacity=192, shard_key=shard_key)
    rng = np.random.default_rng(1)
    raw = []
    for _ in range(5):
        if data == "normal":
            batch = {"x": rng.standard_normal(16),
                     "y": rng.standard_normal(16)}
        else:
            batch = {"x": rng.integers(0, 9, 16).astype(float),
                     "y": rng.integers(-50, 50, 16).astype(float)}
        raw.append(batch["y"])
        ref.append(batch)
        sh.append(batch)
    win = np.concatenate(raw)[32:64]           # latest complete 32-window
    direct = {"count": float(len(win)), "sum": win.sum(),
              "avg": win.mean(), "min": win.min(), "max": win.max()}[fn]
    got = sh.window_aggregate(32, fn, "y")
    assert ref.window_aggregate(32, fn, "y") == pytest.approx(direct)
    assert got == pytest.approx(direct)
    if rings == 1 or data == "integer":
        # one ring is the reference layout; integer-valued partial sums
        # add up exactly, so every layout matches it bit for bit
        assert got == ref.window_aggregate(32, fn, "y")
    # repeat ticks over the same window are memoized (the rolling path)
    before = sh.agg_computes
    sh.window_aggregate(32, fn, "y")
    assert sh.agg_computes == before and sh.agg_cache_hits == 1


def test_sharded_ops_via_bql_are_shard_transparent():
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "vitals.stream", ("hr",),
                            capacity=512, shards=4, num_engines=2,
                            block_rows=4)
    assert sorted(e for e in bd.engines if e.startswith("streamstore")) \
        == ["streamstore0", "streamstore1"]
    sh.append({"hr": np.arange(64, dtype=float)})
    snap = bd.query("bdstream(snapshot(vitals.stream))").value
    np.testing.assert_array_equal(np.asarray(snap.columns["hr"]),
                                  np.arange(64))
    agg = bd.query("bdstream(aggregate(window(vitals.stream, 32),"
                   " avg(hr)))").value
    assert float(agg.attrs["avg_hr"][0]) == pytest.approx(47.5)
    # gathered window casts into the array island like any window view
    r = bd.query("bdarray(aggregate(bdcast(bdstream(window("
                 "vitals.stream, 32)), w_arr,"
                 " '<hr:double>[tick=0:31,32,0]', array), max(hr)))")
    assert float(r.value.attrs["max_hr"][0]) == 63.0
    # the handle lives on every participating engine; plans pin to home
    assert bd.engines["streamstore1"].get("vitals.stream") is sh
    assert sh.home_engine == "streamstore0"


def test_sharded_drop_accounting_sums_shards():
    sh = _mk(2, capacity=16, block_rows=2)
    sh.append({"x": np.arange(40, dtype=float),
               "y": np.arange(40, dtype=float)})
    assert sh.total_appended == 40
    assert sh.total_dropped == 40 - sh.num_rows > 0
    stats = sh.stats()
    assert stats["dropped"] == sum(s["dropped"]
                                   for s in stats["shards"].values())


# -- live shard migration -----------------------------------------------------
def test_stream_route_moves_live_state():
    bd = default_deployment(stream_engines=2)
    src = bd.engines["streamstore0"]
    dst = bd.engines["streamstore1"]
    stream = bd.register_stream("streamstore0", "solo.stream", ("x",),
                                capacity=8)
    stream.append({"x": np.arange(20, dtype=float)})   # 12 dropped
    result = bd.migrator.migrate(src, "solo.stream", dst, "solo.stream",
                                 MigrationParams(method="stream"))
    assert result.method == "stream" and result.rows == 8
    assert not src.has("solo.stream")                  # moved, not copied
    moved = dst.get("solo.stream")
    assert moved.total_appended == 20 and moved.total_dropped == 12
    np.testing.assert_array_equal(
        np.asarray(moved.snapshot().columns["seq"]), np.arange(12, 20))
    moved.append({"x": [99.0]})                        # watermark continues
    assert moved.total_appended == 21
    # rolling state travelled too: O(1) window sums still correct
    # (window [14, 21) holds values 14..19 and the new 99)
    assert moved.window_aggregate(7, "sum", "x") == pytest.approx(
        np.arange(14, 20).sum() + 99)


def test_stream_route_rejects_non_streams():
    bd = default_deployment(stream_engines=2)
    bd.engines["streamstore0"].put("not_a_stream", np.arange(3))
    with pytest.raises(MigrationException):
        bd.migrator.migrate(bd.engines["streamstore0"], "not_a_stream",
                            bd.engines["streamstore1"], "x",
                            MigrationParams(method="stream"))
    stream = bd.register_stream("streamstore0", "s2", ("x",), capacity=8)
    stream.append({"x": [1.0]})
    with pytest.raises(MigrationException):
        bd.migrator.migrate(bd.engines["streamstore0"], "s2",
                            bd.engines["hoststore0"], "s2",
                            MigrationParams(method="stream"))


def test_live_migration_preserves_standing_query_continuity():
    """Move a shard between StreamEngines mid-standing-query: seq/drop
    accounting is preserved and the query's next tick both executes and
    still hits the plan cache (the logical placement didn't change)."""
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "vitals.stream", ("hr",),
                            capacity=256, shards=4, num_engines=2,
                            block_rows=8)
    cq = bd.register_continuous(
        "bdstream(aggregate(window(vitals.stream, 32), avg(hr)))",
        name="hr_avg")
    rng = np.random.default_rng(2)
    sh.append({"hr": rng.standard_normal(48)})
    bd.streams.tick()
    assert cq.executions == 1 and cq.errors == 0
    appended, dropped = sh.total_appended, sh.total_dropped
    move = bd.rebalance_stream("vitals.stream", shard=0,
                               to_engine="streamstore1")
    assert move["from"] == "streamstore0" and move["to"] == "streamstore1"
    assert sh.total_appended == appended and sh.total_dropped == dropped
    assert sh.shard_engines()[0] == "streamstore1"
    # the catalog followed the shard
    assert bd.catalog.engine_for_object(
        "vitals.stream@shard0").name == "streamstore1"
    sh.append({"hr": rng.standard_normal(48)})
    bd.streams.tick()
    assert cq.executions == 2 and cq.errors == 0
    assert cq.cache_hits >= 1                    # plan survived the move
    assert bd.streams.status()["rebalances"][0]["shard"] == 0


def test_rebalance_hook_moves_shard_off_lopsided_engine():
    """Skewed shard-key traffic makes the Monitor's per-shard stats
    lopsided; admin.rebalance() then moves a shard off the hot engine."""
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "skew.stream",
                            ("patient", "hr"), capacity=2048, shards=4,
                            shard_key="patient", num_engines=2)
    rng = np.random.default_rng(3)
    for _ in range(6):
        # patient ids hash (floor(|v|) % 4) onto mostly shard 1
        patient = np.where(rng.random(128) < 0.9, 1.0,
                           rng.integers(0, 4, 128).astype(float))
        sh.append({"patient": patient,
                   "hr": 75 + rng.standard_normal(128)})
        bd.streams.tick()
    assert bd.monitor.lopsided_shards("skew.stream") == [1]
    outcome = admin.rebalance(bd)
    assert len(outcome["moves"]) == 1 and not outcome["skipped"]
    move = outcome["moves"][0]
    assert move["stream"] == "skew.stream"
    # load is evener now: the two engines no longer share the hot shard
    engines = [s["engine"] for s in sh.shard_stats().values()]
    hot_engine = sh.shard_stats()[1]["engine"]
    assert engines.count(hot_engine) < 3
    # no further move helps (the hot shard dominates on its own engine):
    # the hook reports the stream as skipped rather than thrashing shards
    again = admin.rebalance(bd)
    assert again["moves"] == []
    assert [s["stream"] for s in again["skipped"]] == ["skew.stream"]


def test_lopsided_detection_works_with_two_shards():
    """With the upper median a 2-shard stream could never look lopsided
    (the hot shard IS the median); the lower median flags it."""
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "duo.stream", ("k", "v"),
                            capacity=1024, shards=2, shard_key="k",
                            num_engines=2)
    rng = np.random.default_rng(6)
    # every key is odd -> everything hashes onto shard 1
    sh.append({"k": np.ones(256), "v": rng.standard_normal(256)})
    bd.streams.tick()
    assert bd.monitor.lopsided_shards("duo.stream") == [1]


def test_rebalance_refuses_useless_moves():
    bd = default_deployment()
    bd.register_stream("streamstore0", "flat.stream", ("x",),
                       capacity=512, shards=2, num_engines=2,
                       block_rows=2)
    sh = bd.engines["streamstore0"].get("flat.stream")
    sh.append({"x": np.arange(64, dtype=float)})
    with pytest.raises(ValueError):
        bd.streams.rebalance("flat.stream")      # 1 shard/engine: no gain
    with pytest.raises(ValueError):
        bd.streams.rebalance("nonexistent.stream")
    with pytest.raises(ValueError):              # bad explicit shard
        bd.streams.rebalance("flat.stream", shard=9,
                             to_engine="streamstore1")
    with pytest.raises(ValueError):              # bad explicit engine
        bd.streams.rebalance("flat.stream", shard=0,
                             to_engine="streamstoreX")


def test_sharded_stream_resolves_on_anchor_engine():
    """The caller-named engine must hold the handle even when the shards
    spread over streamstore0..N-1 (stream_mimic_waveforms resolves the
    stream through the anchor engine)."""
    from repro.data.mimic import stream_mimic_waveforms
    bd = default_deployment(stream_engines=3)
    sh = bd.register_stream("streamstore2", "anchored.stream", ("x",),
                            capacity=256, shards=2, num_engines=2)
    assert bd.engines["streamstore2"].get("anchored.stream") is sh
    assert sh.shard_engines() == ["streamstore0", "streamstore1"]
    bd2 = default_deployment(stream_engines=3)
    ran = list(stream_mimic_waveforms(bd2, batch_rows=16, num_batches=2,
                                      engine_name="streamstore2",
                                      shards=2))
    assert len(ran) == 2 and ran[-1]["rows"] == 32


def test_stream_route_refuses_self_move():
    bd = default_deployment()
    stream = bd.register_stream("streamstore0", "self.stream", ("x",),
                                capacity=8)
    stream.append({"x": [1.0, 2.0]})
    eng = bd.engines["streamstore0"]
    with pytest.raises(MigrationException):
        bd.migrator.migrate(eng, "self.stream", eng, "self.stream",
                            MigrationParams(method="stream"))
    assert eng.has("self.stream")            # buffer untouched


def test_rebalance_finds_moves_beyond_busiest_engine():
    """Loads A=hot(unmovable alone), B=two light shards, C=idle: the
    improving move donates a light shard from B to C even though B is
    not the busiest engine."""
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "tri.stream", ("k", "v"),
                            capacity=4096, shards=3, shard_key="k",
                            num_engines=3)
    # key m hashes to shard m % 3; shards land on engines 0,1,2 — pile
    # weight on shard 0 (engine A) and split light load on shards 1,2...
    # then co-locate shards 1 and 2 by moving shard 2 onto engine 1
    bd.streams.rebalance("tri.stream", shard=2, to_engine="streamstore1")
    rng = np.random.default_rng(5)
    k = np.concatenate([np.zeros(600), np.ones(90),
                        np.full(90, 2.0)])
    sh.append({"k": k, "v": rng.standard_normal(len(k))})
    bd.streams.tick()
    # engine loads now: ss0=600 (hot, single shard), ss1=180, ss2=0
    move = bd.streams.rebalance("tri.stream")
    assert move["from"] == "streamstore1" and move["to"] == "streamstore2"


def test_empty_batch_append_is_a_noop():
    s = Stream("e", ("x",), capacity=8)
    assert s.append({"x": []}) == {"appended": 0, "dropped": 0, "rows": 0}
    s.append({"x": [1.0, 2.0]})
    assert s.append({"x": []})["rows"] == 2
    sh = _mk(2)
    assert sh.append({"x": [], "y": []})["appended"] == 0
    assert sh.num_rows == 0


def test_rolling_sums_reanchor_each_ring_generation():
    """Once per ring generation the cumulative slots are rewritten as
    buffered-only prefix sums, so the running totals stay bounded and
    range_sum precision can't drift over a long-lived stream."""
    s = Stream("r", ("x",), capacity=8)
    s.append({"x": np.full(8, 1e9)})
    assert s.window_aggregate(8, "sum", "x") == pytest.approx(8e9)
    ring, = s.rings
    assert "x" in ring.export_state()["cum"]   # lazily built on first use
    s.append({"x": np.full(56, 1e9)})          # crosses generations
    s.append({"x": np.arange(8, dtype=float)})  # crosses again
    assert ring.export_state()["running"]["x"] == pytest.approx(
        np.arange(8).sum())
    assert s.window_aggregate(4, "sum", "x") == pytest.approx(4 + 5 + 6 + 7)
    assert s.window_aggregate(8, "sum", "x") == pytest.approx(28.0)


def test_rolling_sums_stay_precise_with_large_magnitudes():
    """Steady small-batch ingest of epoch-millisecond-sized values: the
    O(1) fast path must keep matching a directly materialized window
    (without re-anchoring, the lifetime running total exceeds 2**53 and
    the prefix-sum subtraction visibly drifts)."""
    rng = np.random.default_rng(0)
    s = Stream("ts", ("t",), capacity=256)
    s.append({"t": rng.uniform(1e12, 2e12, 128)})
    s.window_aggregate(128, "sum", "t")        # build the cum ring early
    for _ in range(2000):                      # 128k rows, 64 per batch
        s.append({"t": rng.uniform(1e12, 2e12, 64)})
    k = s.total_appended // 128 - 1
    cols = s.rings[0].export_state()["cols"]   # raw float64 ring values
    seqs = cols["__seq"]
    exact = float(cols["t"][(seqs >= k * 128)
                            & (seqs < (k + 1) * 128)].sum())
    assert abs(s.window_aggregate(128, "sum", "t") - exact) < 1.0


def test_scatter_vectorized_path_matches_segment_path():
    """A batch spanning many small blocks takes the vectorized owner
    path; distribution and gather must match the segment path exactly."""
    ref = Stream("s", ("x", "y"), 4096)
    sh_seg = _mk(3, capacity=4096)
    sh_vec = _mk(3, capacity=4096)
    rng = np.random.default_rng(8)
    batch = {"x": rng.standard_normal(600), "y": rng.standard_normal(600)}
    ref.append(batch)
    for part in (dict(x=batch["x"][:100], y=batch["y"][:100]),
                 dict(x=batch["x"][100:], y=batch["y"][100:])):
        sh_seg.append(part)                     # 25 blocks: segment path
    sh_vec.append(batch)                        # 150 blocks: vectorized
    for view in (lambda s: s.snapshot().columns["y"],
                 lambda s: s.window(128).attrs["x"]):
        np.testing.assert_array_equal(np.asarray(view(ref)),
                                      np.asarray(view(sh_vec)))
        np.testing.assert_array_equal(np.asarray(view(sh_vec)),
                                      np.asarray(view(sh_seg)))


def test_nan_shard_key_routes_deterministically():
    import warnings
    sh = _mk(2, fields=("k", "v"), shard_key="k")
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # any RuntimeWarning fails
        sh.append({"k": [1.0, float("nan"), float("inf"),
                         float("-inf"), 2.0],
                   "v": [10.0, 20.0, 30.0, 40.0, 50.0]})
    # non-finite keys land on shard 0; the gather still sees every row
    np.testing.assert_array_equal(
        np.asarray(sh.snapshot().columns["v"]),
        [10.0, 20.0, 30.0, 40.0, 50.0])


def test_num_engines_respected_in_grown_deployment():
    """A deployment whose streaming island is already larger must still
    honor the requested num_engines spread."""
    bd = default_deployment(stream_engines=4)
    sh = bd.register_stream("streamstore0", "narrow.stream", ("x",),
                            capacity=256, shards=4, num_engines=2)
    assert sh.shard_engines() == ["streamstore0", "streamstore1",
                                  "streamstore0", "streamstore1"]


def test_lopsided_detection_tracks_current_load_not_lifetime():
    """Late-onset skew: a long-balanced stream whose traffic suddenly
    piles onto one shard.  Lifetime appended/dropped counters stay
    near-balanced (history dominates), so the old detector missed it;
    the per-tick EWMA flags the newly hot shard within a few ticks, and
    the formerly busy shard's load decays instead of charging its donor
    engine forever."""
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "onset.stream", ("k", "v"),
                            capacity=65536, shards=2, shard_key="k",
                            num_engines=2)
    rng = np.random.default_rng(9)
    # phase 1: 10 balanced ticks (alternating keys -> both shards even)
    for _ in range(10):
        sh.append({"k": np.tile([0.0, 1.0], 64),
                   "v": rng.standard_normal(128)})
        bd.streams.tick()
    assert bd.monitor.lopsided_shards("onset.stream") == []
    # phase 2: traffic flips entirely onto shard 1
    for _ in range(8):
        sh.append({"k": np.ones(128), "v": rng.standard_normal(128)})
        bd.streams.tick()
    stats = bd.monitor.shard_stats["onset.stream"]
    lifetime = {i: Monitor.shard_load(st) for i, st in stats.items()}
    # the lifetime view still looks balanced (under the 3x threshold)...
    assert lifetime[1] < 3.0 * lifetime[0]
    # ...but the EWMA sees the current skew and flags shard 1
    assert bd.monitor.lopsided_shards("onset.stream") == [1]
    loads = bd.monitor.shard_loads("onset.stream")
    assert loads[1] > 3.0 * loads[0]
    # the idle shard's load decayed well below its lifetime ingest —
    # its engine is no longer charged for historical rows
    assert loads[0] < 0.2 * lifetime[0]


def test_rebalance_uses_current_loads_after_traffic_shift():
    """The mover and the detector share the EWMA view: after the shift,
    rebalance moves the *currently* hot shard off its engine even though
    lifetime counters would call the placement fine."""
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "shift.stream", ("k", "v"),
                            capacity=65536, shards=4, shard_key="k",
                            num_engines=2)
    rng = np.random.default_rng(10)
    for _ in range(10):                    # balanced history, all shards
        sh.append({"k": np.tile([0.0, 1.0, 2.0, 3.0], 32),
                   "v": rng.standard_normal(128)})
        bd.streams.tick()
    for _ in range(8):                     # now only shard 1 is hot
        sh.append({"k": np.ones(256), "v": rng.standard_normal(256)})
        bd.streams.tick()
    hot_engine = sh.shard_stats()[1]["engine"]
    move = bd.streams.rebalance("shift.stream")
    # the currently hot engine donates (lifetime counters would have
    # weighed all four shards near-equal and could pick either side)
    assert move["from"] == hot_engine


# -- background tick driver ---------------------------------------------------
def test_background_driver_ticks_and_stops_leak_free():
    bd = default_deployment()
    bd.register_stream("streamstore0", "t.stream", ("x",), capacity=64)
    stream = bd.engines["streamstore0"].get("t.stream")
    cq = bd.register_continuous("bdstream(snapshot(t.stream))",
                                name="snap")
    stream.append({"x": [1.0, 2.0]})
    before = threading.active_count()
    bd.streams.start(interval_seconds=0.01)
    with pytest.raises(RuntimeError):            # double-start refused
        bd.streams.start(interval_seconds=0.01)
    deadline = time.monotonic() + 5.0
    while bd.streams.driver_ticks < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert bd.streams.driver_running
    assert bd.streams.stop()
    assert not bd.streams.driver_running
    ticks = bd.streams.ticks
    time.sleep(0.05)
    assert bd.streams.ticks == ticks             # really stopped
    assert cq.executions >= 3
    assert not any(t.name == "stream-tick-driver"
                   for t in threading.enumerate())
    assert threading.active_count() <= before + 1
    # restart works after a clean stop; stop with no driver reports False
    bd.streams.start(interval_seconds=0.01)
    assert bd.streams.stop()
    assert bd.streams.stop() is False
    st = bd.streams.status()["background"]
    assert st["running"] is False and st["driver_ticks"] >= 3


def test_background_driver_survives_tick_exceptions(monkeypatch):
    """An unexpected error outside per-query isolation is recorded but
    must not kill the daemon thread."""
    bd = default_deployment()
    boom = {"left": 2}
    real_tick = bd.streams.tick

    def flaky_tick():
        if boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("injected")
        return real_tick()

    monkeypatch.setattr(bd.streams, "tick", flaky_tick)
    bd.streams.start(interval_seconds=0.01)
    deadline = time.monotonic() + 5.0
    while bd.streams.driver_ticks < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert bd.streams.driver_running          # survived the bad ticks
    bd.streams.stop()
    st = bd.streams.status()["background"]
    assert st["driver_errors"] == 2
    assert "injected" in st["last_driver_error"]
    assert bd.streams.ticks >= 2              # real ticks resumed


# -- admin surface ------------------------------------------------------------
def test_status_reports_per_shard_stats():
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "vitals.stream", ("hr",),
                            capacity=512, shards=4, num_engines=2,
                            block_rows=4)
    sh.append({"hr": np.arange(64, dtype=float)})
    bd.streams.tick()
    st = admin.status(bd)
    info = st["streams"]["streams"]["vitals.stream"]
    assert set(info["shards"]) == {0, 1, 2, 3}
    for shard in info["shards"].values():
        assert {"engine", "rows", "appended", "dropped"} <= set(shard)
    assert info["engine"] == ["streamstore0", "streamstore1",
                              "streamstore0", "streamstore1"]
    assert st["streams"]["background"]["running"] is False
    # shard rings don't show up as top-level streams
    assert not any("@shard" in name
                   for name in st["streams"]["streams"])
    # the Monitor holds the same per-shard snapshot (rebalance signal)
    assert set(bd.monitor.shard_stats["vitals.stream"]) == {0, 1, 2, 3}
