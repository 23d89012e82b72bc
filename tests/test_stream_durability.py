"""Deterministic durability suite: segment-log framing, torn-tail
repair, checkpoint/recover bit-identity for every stream shape, the
dead-letter side stream, tick-cadence checkpoints, the replay(S) op,
and — the heart of the layer — an **exhaustive crash-point sweep**:
count the workload's crash surface with a never-firing countdown, then
kill at every single site and assert recover() lands on some prefix of
the uncrashed run's fingerprint history, and that continuing from that
prefix reconverges bit-identically to the uncrashed final state.

The hypothesis generalization of the sweep (random schedules, random
crash sites, shrinking) lives in tests/test_stream_crash_points.py.
The flake-hunter workflow re-runs both files 5x at REPRO_MAX_WORKERS=8.
"""
import os
import threading

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core.api import default_deployment
from repro.runtime import fault
from repro.stream import durability as dur
from repro.stream.engine import Stream


@pytest.fixture(autouse=True)
def _disarm():
    yield
    fault.disarm_crash_points()


def _feed_plain(stream, ops):
    for v in ops:
        stream.append({"a": v})


def _plain_ops(rng, n=6, cap=32):
    return [rng.normal(size=int(k))
            for k in rng.integers(1, cap + 5, n)]


# -- segment log -------------------------------------------------------------

def test_segment_log_roundtrip_and_roll(tmp_path):
    log = dur.SegmentLog(str(tmp_path), ("a", "b"), segment_bytes=200)
    rng = np.random.default_rng(0)
    batches = [{f: rng.normal(size=5) for f in ("a", "b")}
               for _ in range(7)]
    for i, cols in enumerate(batches):
        assert log.append(dur.KIND_SHARD, i * 5, 5, cols, 5) == i
    assert len(log._segments()) > 1          # tiny cap forced rolls
    recs = dur.SegmentLog(str(tmp_path), ("a", "b")).scan()
    assert [r.lsn for r in recs] == list(range(7))
    for rec, cols in zip(recs, batches):
        for f in ("a", "b"):
            np.testing.assert_array_equal(rec.cols[f], cols[f])
    # scan from a mid lsn
    assert [r.lsn for r in log.scan(start_lsn=4)] == [4, 5, 6]


def test_segment_log_torn_tail_detected_and_repaired(tmp_path):
    log = dur.SegmentLog(str(tmp_path), ("a",))
    log.append(dur.KIND_SHARD, 0, 3, {"a": np.ones(3)}, 3)
    log.append(dur.KIND_SHARD, 3, 2, {"a": np.ones(2)}, 2)
    log.close()
    # tear the last record's payload
    _, path = log._segments()[-1]
    os.truncate(path, os.path.getsize(path) - 4)
    assert [r.lsn for r in
            dur.SegmentLog(str(tmp_path), ("a",)).scan()] == [0]
    # a reopened log repairs the tear and reuses the lsn
    log2 = dur.SegmentLog(str(tmp_path), ("a",))
    assert log2.next_lsn == 1
    log2.append(dur.KIND_SHARD, 3, 4, {"a": np.zeros(4)}, 4)
    recs = log2.scan()
    assert [r.lsn for r in recs] == [0, 1]
    assert recs[1].nrows == 4


def test_segment_log_crc_corruption_stops_scan(tmp_path):
    log = dur.SegmentLog(str(tmp_path), ("a",))
    for i in range(3):
        log.append(dur.KIND_SHARD, i, 1, {"a": np.full(1, i)}, 1)
    log.close()
    _, path = log._segments()[0]
    with open(path, "r+b") as f:           # flip one payload byte of rec 1
        f.seek(dur._HDR.size * 2 + 8 + 3)
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ 0xFF]))
    assert [r.lsn for r in
            dur.SegmentLog(str(tmp_path), ("a",)).scan()] == [0]


def test_truncate_from_and_prune(tmp_path):
    log = dur.SegmentLog(str(tmp_path), ("a",), segment_bytes=64)
    for i in range(10):
        log.append(dur.KIND_SHARD, i, 1, {"a": np.full(1, i)}, 1)
    log.truncate_from(6)
    assert [r.lsn for r in log.scan()] == list(range(6))
    assert log.next_lsn == 6
    log.append(dur.KIND_SHARD, 6, 1, {"a": np.zeros(1)}, 1)
    assert [r.lsn for r in log.scan()] == list(range(7))
    nseg = len(log._segments())
    log.prune_below(5)
    assert len(log._segments()) < nseg
    assert [r.lsn for r in log.scan(5)] == [5, 6]


# -- checkpoint/recover bit-identity per stream shape ------------------------

def test_plain_recover_bit_identical(tmp_path):
    rng = np.random.default_rng(1)
    ops = _plain_ops(rng)
    s = Stream("t", ("a",), 32)
    h = dur.attach(s, str(tmp_path))
    for i, v in enumerate(ops):
        s.append({"a": v})
        if i == 2:
            h.checkpoint()
    r = dur.recover(str(tmp_path))
    assert dur.fingerprint(r.stream) == dur.fingerprint(s)
    assert r.checkpoint_step == 1 and r.rows_replayed > 0
    # rolling aggregates reproduce too, not just counters
    assert (s.window(8).aggregate("sum", "a")
            == r.stream.window(8).aggregate("sum", "a"))


def test_plain_recover_without_checkpoint(tmp_path):
    s = Stream("t", ("a",), 16)
    dur.attach(s, str(tmp_path))
    _feed_plain(s, _plain_ops(np.random.default_rng(2), n=4, cap=16))
    r = dur.recover(str(tmp_path))
    assert r.checkpoint_step is None
    assert dur.fingerprint(r.stream) == dur.fingerprint(s)


def test_sharded_recover_bit_identical(tmp_path):
    rng = np.random.default_rng(3)
    ss = Stream("w", ("a", "b"), 64, shards=["e0", "e1", "e2"],
                block_rows=8)
    h = dur.attach(ss, str(tmp_path))
    for i in range(9):
        n = int(rng.integers(1, 30))
        ss.append({"a": rng.normal(size=n), "b": rng.normal(size=n)})
        if i == 4:
            h.checkpoint()
    r = dur.recover(str(tmp_path))
    assert dur.fingerprint(r.stream) == dur.fingerprint(ss)
    # seq assignment is part of the identity: gathers agree exactly
    np.testing.assert_array_equal(
        np.asarray(ss.window(40).attrs["a"]),
        np.asarray(r.stream.window(40).attrs["a"]))


def test_event_time_recover_with_late_and_flush(tmp_path):
    rng = np.random.default_rng(4)
    s = Stream("e", ("ts", "v"), 64, ts_field="ts", max_delay=2.0)
    h = dur.attach(s, str(tmp_path))
    ts = np.arange(40, dtype=float)
    ts[5], ts[6] = ts[6], ts[5]            # bounded disorder
    for k in range(0, 40, 8):
        s.append({"ts": ts[k:k + 8], "v": rng.normal(size=8)})
        if k == 16:
            h.checkpoint()
    s.append({"ts": np.array([0.5]), "v": np.array([9.0])})   # late
    s.flush(50.0)
    r = dur.recover(str(tmp_path))
    assert dur.fingerprint(r.stream) == dur.fingerprint(s)
    assert r.stream.total_late == 1
    assert r.stream.watermark == 50.0


def test_sharded_event_time_recover(tmp_path):
    rng = np.random.default_rng(5)
    ss = Stream("x", ("ts", "v"), 64, shards=["e0", "e1"], block_rows=8,
                ts_field="ts", max_delay=4.0)
    h = dur.attach(ss, str(tmp_path))
    ts = np.arange(48, dtype=float)
    for k in range(0, 48, 6):
        ss.append({"ts": ts[k:k + 6], "v": rng.normal(size=6)})
        if k == 24:
            h.checkpoint()
    ss.flush(60.0)
    r = dur.recover(str(tmp_path))
    assert dur.fingerprint(r.stream) == dur.fingerprint(ss)


def test_recover_after_wal_prune(tmp_path):
    """keep-last-k pruning must never strand a retained checkpoint
    without its log tail."""
    rng = np.random.default_rng(6)
    s = Stream("p", ("a",), 16)
    # two 8-row records (field + seq column, 165 bytes each) per segment
    h = dur.attach(s, str(tmp_path), keep=2, segment_bytes=384)
    for i in range(30):
        s.append({"a": rng.normal(size=8)})
        if i % 10 == 9:
            h.checkpoint()
    assert h.manager.all_steps() == [2, 3]   # keep-last-2 held
    assert h.stats()["segments"] < 8         # wal actually pruned
    r = dur.recover(str(tmp_path))
    assert dur.fingerprint(r.stream) == dur.fingerprint(s)


# -- exhaustive crash-point sweep --------------------------------------------

def _crash_workload(tmp_path, ops):
    """The canonical sweep workload: plain durable stream, a mid-run
    blocking checkpoint."""
    s = Stream("t", ("a",), 32)
    h = dur.attach(s, str(tmp_path))
    for i, v in enumerate(ops):
        s.append({"a": v})
        if i == 2:
            h.checkpoint()
    return s


def test_crash_at_every_point_recovers_a_prefix(tmp_path):
    """Kill the workload at EVERY crash site (log write boundaries,
    checkpoint begin/promote/gc/prune) and require: (1) recover() is
    bit-identical to some prefix of the uncrashed run, (2) re-running
    the remaining ops reconverges to the uncrashed final state, (3) a
    second recovery of the continued log also matches — the log the
    continuation wrote is itself consistent."""
    rng = np.random.default_rng(7)
    ops = _plain_ops(rng)
    ref = Stream("t", ("a",), 32)
    snaps = [dur.fingerprint(ref)]
    for v in ops:
        ref.append({"a": v})
        snaps.append(dur.fingerprint(ref))

    fault.arm_crash_point("stream/*", at_hit=10 ** 9)
    _crash_workload(tmp_path / "count", ops)
    surface = len(fault.disarm_crash_points()["hits"])
    assert surface >= len(ops), "crash surface suspiciously small"

    for k in range(1, surface + 1):
        d = tmp_path / f"k{k}"
        fault.arm_crash_point("stream/*", at_hit=k)
        try:
            _crash_workload(d, ops)
            crashed = False
        except fault.SimulatedCrash:
            crashed = True
        report = fault.disarm_crash_points()
        assert crashed and report["fired"] is not None, k
        r = dur.recover(str(d))
        fp = dur.fingerprint(r.stream)
        assert fp in snaps, \
            f"hit {k} ({report['fired']}): no prefix matches"
        p = snaps.index(fp)
        dur.attach(r.stream, str(d))
        for v in ops[p:]:
            r.stream.append({"a": v})
        assert dur.fingerprint(r.stream) == snaps[-1], k
        assert dur.fingerprint(dur.recover(str(d)).stream) == snaps[-1]


def test_crash_inside_checkpoint_manager(tmp_path):
    """Kill between the manifest write and the atomic promote, and
    between promote and gc: the previous checkpoint must stay live and
    recovery must still converge."""
    rng = np.random.default_rng(8)
    ops = _plain_ops(rng)
    for point in ("checkpoint/promote", "checkpoint/gc"):
        d = tmp_path / point.replace("/", "_")
        fault.arm_crash_point(point, at_hit=1)
        with pytest.raises(fault.SimulatedCrash):
            _crash_workload(d, ops)
        fault.disarm_crash_points()
        r = dur.recover(str(d))
        dur.attach(r.stream, str(d))
        # finish the run from wherever the prefix landed: the recovered
        # stream accepts ingest and a fresh checkpoint cleanly
        r.stream.append({"a": np.ones(5)})
        r.stream._durable.checkpoint()
        r2 = dur.recover(str(d))
        assert dur.fingerprint(r2.stream) == dur.fingerprint(r.stream)


def test_sharded_crash_cuts_incomplete_block(tmp_path):
    """A kill between two shard-lane log appends leaves a block only
    partially logged; recovery must cut it (and everything after) on
    every lane, then continue consistently."""
    rng = np.random.default_rng(9)

    def build(d):
        ss = Stream("w", ("a",), 64, shards=["e0", "e1"], block_rows=4)
        dur.attach(ss, str(d))
        return ss

    batches = [rng.normal(size=10) for _ in range(3)]  # span both shards

    # uncrashed reference: fingerprint after every append
    ref = Stream("w", ("a",), 64, shards=["e0", "e1"], block_rows=4)
    snaps = [dur.fingerprint(ref)]
    for v in batches:
        ref.append({"a": v})
        snaps.append(dur.fingerprint(ref))

    # count the crash surface
    fault.arm_crash_point("stream/log:*", at_hit=10 ** 9)
    ss = build(tmp_path / "count")
    for v in batches:
        ss.append({"a": v})
    surface = len(fault.disarm_crash_points()["hits"])
    assert surface >= 2 * len(batches)        # >= one site per lane

    for k in range(1, surface + 1):
        d = tmp_path / f"k{k}"
        ss = build(d)
        fault.arm_crash_point("stream/log:*", at_hit=k)
        try:
            for v in batches:
                ss.append({"a": v})
        except fault.SimulatedCrash:
            pass
        fault.disarm_crash_points()
        r = dur.recover(str(d))
        rs = r.stream
        # whatever survived is a whole-block prefix of the reference:
        # incomplete blocks were cut, so some append-prefix matches
        assert rs.total_appended % 10 == 0
        assert dur.fingerprint(rs) in snaps, k
        # and the repaired log re-recovers to the same state
        assert (dur.fingerprint(dur.recover(str(d)).stream)
                == dur.fingerprint(rs))


def _denamed(fp):
    fp = dict(fp, name=None)
    fp["shards"] = [dict(d, name=None) for d in fp["shards"]]
    return fp


@pytest.mark.parametrize("cause,rings", [("stall", 1), ("stage", 1),
                                         ("commit", 2)])
def test_seq_hole_is_durable(tmp_path, cause, rings):
    """A block that never fully publishes — its producer died holding a
    commit ticket (stolen after a stall, the frontier reaps the block),
    its staging raised, or one ring's write raised — is a permanent seq
    hole.  Its bounds are logged, so recovery and replica catch-up step
    over it as the live frontier did: every acknowledged row after it
    comes back, bit-identically, and nothing is cut."""
    s = Stream("h", ("a",), 256, block_rows=4,
               shards=["e0", "e1"] if rings == 2 else None)
    durable = dur.attach(s, str(tmp_path))
    s.append({"a": np.arange(16.0)})
    # a read replica copied before the hole (the Migrator's copy mode)
    state, lsns = s._checkpoint_snapshot(durable.lane_lsns)
    replica = s.clone("h.replica", state=state)
    replica._replica_lsns = lsns
    if cause == "stall":
        # the hard kill: reserve a seq block and its ticket, never stage
        with s._reserve_lock:
            t, n = s.reserved, 8
            s.reserved += n
            tickets = {0: s._committers[0].issue()}
            s.blocks_reserved += 1
            s.rows_reserved += n
        with s._frontier:
            s._pending_blocks[t] = (n, tickets)
        s._committers[0].stall_timeout = 0.2      # keep the test fast
    else:
        target, name = ((s, "_partition") if cause == "stage"
                        else (s.rings[1], "write"))
        real = getattr(target, name)

        def fail_once(*args):
            setattr(target, name, real)
            raise RuntimeError("injected")

        setattr(target, name, fail_once)
        with pytest.raises(RuntimeError, match="injected"):
            s.append({"a": np.arange(100.0, 108.0)})
    acked = [np.arange(16.0)]
    for k in range(3):
        batch = np.arange(10.0) + 200.0 + 10 * k
        s.append({"a": batch})
        acked.append(batch)
    assert s.total_appended == 16 + 8 + 30            # the hole counted
    assert s.ingest_concurrency()["blocks_abandoned"] == (cause == "stall")
    live = np.asarray(s.snapshot().columns["a"])
    assert np.isin(np.concatenate(acked), live).all()

    r = dur.recover(str(tmp_path))
    assert r.truncated_records == 0
    assert dur.fingerprint(r.stream) == dur.fingerprint(s)
    np.testing.assert_array_equal(
        np.asarray(r.stream.snapshot().columns["a"]), live)
    # the repaired log re-recovers to the same state
    assert (dur.fingerprint(dur.recover(str(tmp_path)).stream)
            == dur.fingerprint(s))

    caught = dur.catch_up(replica, durable)
    assert caught["rows"] == len(live) - 16
    assert _denamed(dur.fingerprint(replica)) == \
        _denamed(dur.fingerprint(s))
    assert dur.catch_up(replica, durable)["rows"] == 0   # incremental
    s.close()


# -- dead-letter side stream -------------------------------------------------

def test_dead_letter_stream_queryable_and_replayed(tmp_path):
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "icu.abp", ("ts", "v"),
                           capacity=128, ts_field="ts", max_delay=1.0,
                           durability=str(tmp_path), dead_letter=True)
    s.append({"ts": np.arange(8, dtype=float), "v": np.zeros(8)})
    s.append({"ts": np.array([0.5, 7.5]), "v": np.array([1.0, 2.0])})
    assert s.total_late == 1
    late = bd.query("bdstream(snapshot(icu.abp.__late))").value
    np.testing.assert_array_equal(np.asarray(late.columns["ts"]), [0.5])
    np.testing.assert_array_equal(np.asarray(late.columns["v"]), [1.0])
    # replay preserves the dead letters bit-for-bit
    fp = dur.fingerprint(s)
    s._durable.close()
    bd2 = default_deployment()
    r = bd2.recover_stream("streamstore0", str(tmp_path))
    assert dur.fingerprint(r) == fp
    late2 = bd2.query("bdstream(snapshot(icu.abp.__late))").value
    np.testing.assert_array_equal(np.asarray(late2.columns["ts"]),
                                  [0.5])


def test_dead_letter_without_durability():
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "icu.ecg", ("ts", "v"),
                           capacity=64, ts_field="ts", max_delay=0.5,
                           dead_letter=True)
    s.append({"ts": np.arange(4, dtype=float), "v": np.zeros(4)})
    s.append({"ts": np.array([0.25]), "v": np.array([3.0])})
    late = bd.query("bdstream(snapshot(icu.ecg.__late))").value
    assert np.asarray(late.columns["v"]).tolist() == [3.0]


# -- cadence, API recovery, replay op ----------------------------------------

def test_tick_cadence_checkpoints_and_monitor_feed(tmp_path):
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "vitals.stream",
                           ("patient", "hr"), capacity=1024, shards=2,
                           durability=str(tmp_path),
                           checkpoint_every_rows=200)
    rng = np.random.default_rng(10)
    for _ in range(6):
        s.append({"patient": rng.integers(0, 8, 96).astype(float),
                  "hr": 75 + rng.standard_normal(96)})
        bd.streams.tick()
    s._durable.manager.wait()
    assert s._durable.checkpoints >= 2      # 576 rows / 200 cadence
    snap = bd.monitor.snapshot()
    stats = snap["durability_stats"]["vitals.stream"]
    assert stats["log_rows"] == 576
    assert stats["checkpoints"] >= 2
    # and the full status() render carries the block
    from repro.core import admin
    st = admin.status(bd)
    assert "vitals.stream" in st["streams"]["durability"]


def test_recover_stream_api_sharded(tmp_path):
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "vitals.stream",
                           ("patient", "hr"), capacity=1024, shards=2,
                           durability=str(tmp_path),
                           checkpoint_every_rows=200)
    rng = np.random.default_rng(11)
    for _ in range(4):
        s.append({"patient": rng.integers(0, 8, 96).astype(float),
                  "hr": 75 + rng.standard_normal(96)})
        bd.streams.tick()
    fp = dur.fingerprint(s)
    win = np.asarray(s.window(64).attrs["hr"])
    s._durable.close()
    bd2 = default_deployment()
    r = bd2.recover_stream("streamstore0", str(tmp_path))
    assert dur.fingerprint(r) == fp
    np.testing.assert_array_equal(np.asarray(r.window(64).attrs["hr"]),
                                  win)
    # the recovered stream is live: ingest + standing queries continue
    r.append({"patient": np.zeros(8), "hr": np.full(8, 80.0)})
    out = bd2.query(
        "bdstream(aggregate(window(vitals.stream, 8), avg(hr)))").value
    assert abs(float(np.asarray(
        next(iter(out.attrs.values()))).ravel()[0]) - 80.0) < 1e-12
    assert bd2.monitor.snapshot()["recoveries"]["vitals.stream"][
        "rows_replayed"] >= 0


def test_replay_op_reports_identical(tmp_path):
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "vitals.stream", ("hr",),
                           capacity=256, durability=str(tmp_path))
    rng = np.random.default_rng(12)
    for _ in range(5):
        s.append({"hr": rng.normal(size=20)})
    s._durable.checkpoint()
    s.append({"hr": rng.normal(size=20)})     # tail past the checkpoint
    out = bd.query("bdstream(replay(vitals.stream))").value
    row = {k: float(v[0]) for k, v in out.columns.items()}
    assert row["identical"] == 1.0
    assert row["rows"] == 20.0                # only the tail replays
    assert row["rows_per_second"] > 0.0


def test_replay_op_requires_durability():
    bd = default_deployment()
    bd.register_stream("streamstore0", "plain.stream", ("x",),
                       capacity=16)
    from repro.core.executor import LocalQueryExecutionException
    with pytest.raises(LocalQueryExecutionException,
                       match="no durability"):
        bd.query("bdstream(replay(plain.stream))")


def test_obs_spans_and_metrics_emitted(tmp_path):
    from repro.obs import metrics, trace
    trace.set_enabled(True)
    trace.reset()
    try:
        s = Stream("obs", ("a",), 32)
        h = dur.attach(s, str(tmp_path))
        s.append({"a": np.ones(4)})
        h.checkpoint()
        dur.recover(str(tmp_path))
        names = {r.name for r in trace.spans()}
        assert {"stream/log_append", "stream/checkpoint",
                "stream/replay"} <= names
    finally:
        trace.set_enabled(False)
    text = metrics.prometheus_text()
    assert "repro_stream_log_records_total" in text
    assert "repro_stream_checkpoints_total" in text
    assert "repro_stream_recoveries_total" in text


# -- CheckpointManager async-save regression ---------------------------------

def test_checkpoint_manager_joins_pending_before_next_save(tmp_path):
    """Regression: save(blocking=False) left _pending unjoined, so the
    next save's keep-last-k prune could delete the in-flight .tmp (or
    even the newer promoted step) mid-write.  Now every save joins the
    pending thread first, and _write itself is serialized."""
    mgr = CheckpointManager(str(tmp_path), keep=1)
    release = threading.Event()
    entered = threading.Event()
    real_write = mgr._write

    def slow_write(step, state):
        entered.set()
        release.wait(timeout=10)
        return real_write(step, state)

    mgr._write = slow_write
    mgr.save(1, {"x": np.arange(4)}, blocking=False)
    assert entered.wait(timeout=10)

    done = threading.Event()

    def second_save():
        mgr.save(2, {"x": np.arange(8)})      # blocking
        done.set()

    t = threading.Thread(target=second_save, daemon=True)
    t.start()
    # the blocking save must be parked on the join, not racing ahead
    assert not done.wait(timeout=0.3)
    release.set()
    t.join(timeout=10)
    assert done.is_set()
    assert mgr.all_steps() == [2]             # keep=1 pruned step 1
    assert not [p for p in os.listdir(str(tmp_path))
                if p.endswith(".tmp")]        # no half-written debris
    state, step = mgr.restore({"x": np.zeros(8, dtype=np.int64)})
    assert step == 2
    np.testing.assert_array_equal(state["x"], np.arange(8))


def test_checkpoint_manager_restore_flat(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, {"a": np.arange(3), "b": {"c": np.ones(2)}})
    flat = mgr.restore_flat()
    np.testing.assert_array_equal(flat["a"], np.arange(3))
    np.testing.assert_array_equal(flat["b/c"], np.ones(2))
    with pytest.raises(FileNotFoundError):
        CheckpointManager(str(tmp_path / "empty")).restore_flat()
