"""Race/stress suite for the multi-producer ingest path: N producer
threads x M batches with barrier starts, producers racing ``flush()``,
a live shard migration mid-ingest, and a standing query ticking
throughout.  Every scenario pins the same invariants the property suite
(tests/test_stream_properties.py) checks sequentially:

  * gathered ``seq`` strictly increasing and gap-free (the committed
    frontier never exposes half a batch),
  * each reserved block contiguous in seq and in producer batch order,
  * ``total_dropped + retained == appended``,
  * watermark monotone, rolling sum == recomputed sum.

The flake-hunter workflow re-runs this file 5x at REPRO_MAX_WORKERS=8
(nightly + stream-path PRs) to shake out lock-order regressions."""
import threading

import jax
import numpy as np
import pytest

from repro.core.api import default_deployment
from repro.stream.engine import Stream


def _producer_value(pid: int, batch: int, i: int) -> float:
    """Encode (producer, batch, row) into one float64 so a gathered row
    can be attributed exactly (all components < 1000)."""
    return pid * 1_000_000.0 + batch * 1_000.0 + i


def _check_blocks(values: np.ndarray, batch_rows: int) -> None:
    """Gathered values must decompose into whole batches: contiguous in
    seq, rows in producer order within each block, batches of one
    producer in that producer's send order."""
    assert values.shape[0] % batch_rows == 0
    seen_batches: dict = {}
    for s in range(0, values.shape[0], batch_rows):
        block = values[s:s + batch_rows]
        pid = int(block[0] // 1_000_000)
        batch = int(block[0] // 1_000) % 1_000
        expect = np.array([_producer_value(pid, batch, i)
                           for i in range(batch_rows)])
        np.testing.assert_array_equal(block, expect)
        # batches of one producer appear in send order (the earliest
        # retained batch may be any index when the ring evicted older
        # ones, but later ones must follow consecutively)
        last = seen_batches.get(pid)
        if last is not None:
            assert batch == last + 1, (pid, batch, last)
        seen_batches[pid] = batch


@pytest.mark.parametrize("shard_key", [None, "v"])
def test_barrier_start_producers_keep_seq_gap_free(shard_key):
    """N threads x M batches, all released at once: the gather sees
    every row exactly once, seqs 0..N*M*R-1, each seq block whole."""
    nproducers, nbatches, batch_rows = 6, 30, 64
    bd = default_deployment()
    sh = bd.register_stream(
        "streamstore0", "race.stream", ("v",), capacity=1_000_000,
        shards=4, num_engines=2, block_rows=batch_rows,
        shard_key=shard_key)
    barrier = threading.Barrier(nproducers)
    errors = []

    def feed(pid):
        try:
            with sh.producer(name=f"p{pid}") as producer:
                barrier.wait()
                for b in range(nbatches):
                    producer.append({"v": np.array(
                        [_producer_value(pid, b, i)
                         for i in range(batch_rows)])})
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=feed, args=(pid,))
               for pid in range(nproducers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not errors and not any(t.is_alive() for t in threads)
    total = nproducers * nbatches * batch_rows
    assert sh.total_appended == total == sh.reserved
    snap = sh.snapshot()
    seqs = np.asarray(snap.columns["seq"])
    np.testing.assert_array_equal(seqs, np.arange(total))
    if shard_key is None:
        # block_rows == batch_rows: every batch is one whole seq block
        _check_blocks(np.asarray(snap.columns["v"], np.float64),
                      batch_rows)
    ic = sh.ingest_concurrency()
    assert ic["producers_peak"] == nproducers
    assert ic["producers_open"] == 0
    assert ic["blocks_reserved"] == nproducers * nbatches
    assert ic["rows_reserved"] == total
    assert ic["in_flight_rows"] == 0
    sh.close()


def test_unsharded_stream_concurrent_appends_and_drop_accounting():
    """Plain Stream under producer contention, with a capacity small
    enough to force drops: batches stay whole (a ring write is one
    ordered commit) and total_dropped + retained == appended."""
    stream = Stream("u.race", ("v",), capacity=512)
    nproducers, nbatches, batch_rows = 5, 40, 32
    barrier = threading.Barrier(nproducers)
    errors = []

    def feed(pid):
        try:
            with stream.producer() as producer:
                barrier.wait()
                for b in range(nbatches):
                    producer.append({"v": np.array(
                        [_producer_value(pid, b, i)
                         for i in range(batch_rows)])})
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=feed, args=(pid,))
               for pid in range(nproducers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not errors
    total = nproducers * nbatches * batch_rows
    assert stream.total_appended == total
    assert stream.num_rows == 512
    assert stream.total_dropped + stream.num_rows == total
    # the ring holds the newest rows; batches land whole and in order
    snap = stream.snapshot()
    seqs = np.asarray(snap.columns["seq"])
    np.testing.assert_array_equal(seqs, np.arange(total - 512, total))
    _check_blocks(np.asarray(snap.columns["v"], np.float64), batch_rows)


def test_producers_racing_flush_on_event_time_stream():
    """Concurrent producers + concurrent flush() punctuation on a
    key-hashed event-time stream: the watermark stays monotone, no row
    is lost or duplicated, and the final gather is ts-sorted."""
    bd = default_deployment()
    sh = bd.register_stream(
        "streamstore0", "ev.race", ("ts", "k"), capacity=500_000,
        shards=3, num_engines=2, shard_key="k",
        ts_field="ts", max_delay=4.0)
    nproducers, nbatches, batch_rows = 4, 25, 32
    barrier = threading.Barrier(nproducers + 1)
    stop = threading.Event()
    errors = []
    marks = []

    def feed(pid):
        try:
            rng = np.random.default_rng(pid)
            base = 0.0
            barrier.wait()
            for b in range(nbatches):
                ts = base + np.arange(batch_rows, dtype=float)
                base += batch_rows
                order = np.argsort(ts + rng.uniform(-2, 2, batch_rows))
                sh.append({"ts": ts[order],
                           "k": rng.uniform(0, 30, batch_rows)})
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    def flusher():
        barrier.wait()
        while not stop.is_set():
            sh.flush()
            marks.append(sh.watermark)

    threads = [threading.Thread(target=feed, args=(pid,))
               for pid in range(nproducers)]
    ft = threading.Thread(target=flusher)
    for t in threads + [ft]:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    stop.set()
    ft.join(timeout=10.0)
    assert not errors and not ft.is_alive()
    sh.flush()
    # every non-late row exactly once, ts-sorted in the gather
    appended = sh.total_appended
    assert appended + sh.total_late == nproducers * nbatches * batch_rows
    assert sh._pending_rows == 0
    snap = sh.snapshot()
    seqs = np.asarray(snap.columns["seq"])
    np.testing.assert_array_equal(seqs, np.arange(appended))
    ts_col = np.asarray(snap.columns["ts"])
    assert (np.diff(ts_col) >= 0).all()
    # watermark observed by the racing flusher was monotone
    assert all(a <= b for a, b in zip(marks, marks[1:]))
    sh.close()


def test_live_shard_migration_mid_ingest_with_standing_query():
    """The full chaos scenario: barrier-started producers hammer a
    sharded stream while shard 0 ping-pongs between engines and a
    standing snapshot query ticks on its own thread.  No row lost, no
    row duplicated, no standing-query error, seqs gap-free."""
    nproducers, nbatches, batch_rows = 4, 30, 48
    bd = default_deployment()
    sh = bd.register_stream(
        "streamstore0", "mig.race", ("v",), capacity=1_000_000,
        shards=2, num_engines=2, block_rows=16)
    cq = bd.register_continuous("bdstream(snapshot(mig.race))",
                                name="snap")
    barrier = threading.Barrier(nproducers + 2)
    done = threading.Event()
    errors = []

    def feed(pid):
        try:
            with sh.producer() as producer:
                barrier.wait()
                for b in range(nbatches):
                    producer.append({"v": np.array(
                        [_producer_value(pid, b, i)
                         for i in range(batch_rows)])})
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    def ticker():
        barrier.wait()
        while not done.is_set():
            bd.streams.tick()

    moves = []

    def migrator():
        barrier.wait()
        while not done.is_set():
            dest = ("streamstore1"
                    if sh.shard_engines()[0] == "streamstore0"
                    else "streamstore0")
            sh.migrate_shard(0, bd.migrator, bd.engines, dest)
            moves.append(dest)

    threads = [threading.Thread(target=feed, args=(pid,))
               for pid in range(nproducers)]
    tick_t = threading.Thread(target=ticker)
    mig_t = threading.Thread(target=migrator)
    for t in threads + [tick_t, mig_t]:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    done.set()
    tick_t.join(timeout=10.0)
    mig_t.join(timeout=10.0)
    assert not errors
    assert not any(t.is_alive() for t in threads + [tick_t, mig_t])
    assert len(moves) >= 1 and sh.migrations == len(moves)
    total = nproducers * nbatches * batch_rows
    assert sh.total_appended == total == sh.reserved
    snap = sh.snapshot()
    seqs = np.asarray(snap.columns["seq"])
    np.testing.assert_array_equal(seqs, np.arange(total))
    # a batch is one contiguous seq block, so the seq-ordered gather
    # still decomposes into whole batches even across the moves
    _check_blocks(np.asarray(snap.columns["v"], np.float64), batch_rows)
    assert cq.errors == 0 and cq.executions >= 1
    sh.close()


def test_concurrent_rolling_aggregate_matches_recompute():
    """Rolling cumulative sums survive producer contention: after a
    concurrent ingest burst, the O(1) window aggregate equals a cold
    recompute over the materialized window."""
    bd = default_deployment()
    sh = bd.register_stream("streamstore0", "agg.race", ("v",),
                            capacity=100_000, shards=2, num_engines=2,
                            block_rows=8)
    nproducers, nbatches, batch_rows = 4, 20, 40
    barrier = threading.Barrier(nproducers)
    errors = []

    def feed(pid):
        try:
            barrier.wait()
            rng = np.random.default_rng(pid)
            for _ in range(nbatches):
                sh.append({"v": rng.standard_normal(batch_rows)})
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=feed, args=(pid,))
               for pid in range(nproducers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not errors
    size = 1024
    rolling = sh.window_aggregate(size, "sum", "v")
    # materialize at the ring's float64: the default float32 view alone
    # moves a 1024-row sum by ~1e-6 (ROADMAP D2)
    with jax.enable_x64(True):
        materialized = float(np.asarray(sh.window(size).attrs["v"],
                                        np.float64).sum())
    # cumulative-ring range sums differ from a cold recompute only by
    # float64 rounding (same tolerance the stream bench asserts)
    assert rolling == pytest.approx(materialized, abs=1e-6)
    sh.close()


def test_single_producer_results_bit_identical_to_serial_reference():
    """One producer through the reservation path must behave exactly
    like PR-3's serial scatter: same append result dicts, same gather,
    zero commit waits."""
    rng = np.random.default_rng(0)
    batches = [rng.standard_normal(37) for _ in range(12)]
    bd_a = default_deployment()
    sh = bd_a.register_stream("streamstore0", "s.one", ("v",),
                              capacity=4096, shards=3, num_engines=2,
                              block_rows=8)
    ref = Stream("ref", ("v",), capacity=4096)
    results = []
    for b in batches:
        results.append((sh.append({"v": b}), ref.append({"v": b})))
    for got, want in results:
        assert got["appended"] == want["appended"]
        assert got["dropped"] == want["dropped"]
        assert got["rows"] == want["rows"]
    np.testing.assert_array_equal(
        np.asarray(sh.snapshot().columns["v"]),
        np.asarray(ref.snapshot().columns["v"]))
    assert sh.ingest_concurrency()["commit_waits"] == 0
    assert ref.ingest_concurrency()["commit_waits"] == 0


def test_readers_see_consistent_cuts_under_concurrent_eviction():
    """Small shard rings + concurrent producers + a racing reader: every
    snapshot is a point-in-time cut (all shard locks held for the
    sweep), so gathered seqs stay strictly increasing and decompose
    into whole batches even while eviction churns the rings."""
    nproducers, nbatches, batch_rows = 3, 60, 32
    bd = default_deployment()
    sh = bd.register_stream(
        "streamstore0", "cut.race", ("v",), capacity=16 * batch_rows,
        shards=2, num_engines=2, block_rows=batch_rows)
    barrier = threading.Barrier(nproducers + 1)
    done = threading.Event()
    errors = []

    def feed(pid):
        try:
            with sh.producer() as producer:
                barrier.wait()
                for b in range(nbatches):
                    producer.append({"v": np.array(
                        [_producer_value(pid, b, i)
                         for i in range(batch_rows)])})
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    def reader():
        try:
            barrier.wait()
            while not done.is_set():
                snap = sh.snapshot()
                seqs = np.asarray(snap.columns["seq"])
                if seqs.size == 0:
                    continue
                assert (np.diff(seqs) > 0).all(), "seqs not increasing"
                values = np.asarray(snap.columns["v"], np.float64)
                # whole batches only: each retained seq block is one
                # producer's batch, read in one consistent cut
                for s in range(0, values.shape[0], batch_rows):
                    block = values[s:s + batch_rows]
                    if block.shape[0] < batch_rows:
                        continue
                    pid = int(block[0] // 1_000_000)
                    batch = int(block[0] // 1_000) % 1_000
                    np.testing.assert_array_equal(block, np.array(
                        [_producer_value(pid, batch, i)
                         for i in range(batch_rows)]))
        except Exception as exc:                          # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=feed, args=(pid,))
               for pid in range(nproducers)]
    rt = threading.Thread(target=reader)
    for t in threads + [rt]:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    done.set()
    rt.join(timeout=10.0)
    assert not errors, errors
    total = nproducers * nbatches * batch_rows
    assert sh.total_appended == total
    assert sh.total_dropped + sh.num_rows == total
    sh.close()


def test_hard_killed_producer_cannot_stall_a_shard_lane():
    """PR-5 carry-over regression: a producer that reserves a seq block
    (taking commit tickets on its shard lanes) and then dies without
    ever staging must not wedge the ordered committer.  After one full
    stall interval with zero lane progress the committer *steals* the
    dead tickets, the frontier reaps the abandoned block as a permanent
    hole (staging-failure semantics), a live producer sails through,
    and a revived zombie commit raises instead of double-advancing."""
    import time

    bd = default_deployment()
    s = bd.register_stream("streamstore0", "kill.s", ("v",),
                           capacity=4096, shards=2, num_engines=2,
                           block_rows=4)
    s.append({"v": np.arange(16.0)})          # healthy first batch

    # simulate the hard kill: reserve seqs + tickets, never stage/commit
    with s._reserve_lock:
        t = s.reserved
        n = 8
        s.reserved += n
        touched = s._touched_shards(t, n)
        tickets = {i: s._committers[i].issue() for i in touched}
        s.blocks_reserved += 1
        s.rows_reserved += n
    with s._frontier:
        s._pending_blocks[t] = (n, dict(tickets))
    for c in s._committers:
        c.stall_timeout = 0.2                 # keep the test fast

    done = {}

    def live():
        t0 = time.monotonic()
        s.append({"v": np.arange(100.0, 124.0)})
        done["dt"] = time.monotonic() - t0

    th = threading.Thread(target=live)
    th.start()
    th.join(timeout=30.0)
    assert not th.is_alive(), "live producer stalled behind dead block"
    # bounded by a couple of stall intervals, not forever
    assert done["dt"] < 10.0, done

    ic = s.ingest_concurrency()
    assert ic["commit_steals"] > 0, ic
    assert ic["blocks_abandoned"] == 1, ic
    snap = s.snapshot()
    seqs = np.asarray(snap.columns["seq"])
    assert s.total_appended == 16 + 8 + 24    # hole still counted
    assert seqs[-1] == s.total_appended - 1   # live batch visible
    assert (np.diff(seqs) > 0).all()
    # the hole is exactly the dead block: those seqs never materialize
    assert not np.isin(np.arange(16, 24), seqs).any()

    # a revived zombie must get an error, not a double lane-advance
    from repro.stream.engine import StreamException
    with pytest.raises(StreamException, match="stolen after"):
        s._committers[touched[0]].commit(tickets[touched[0]],
                                         lambda: None)
    s.close()
