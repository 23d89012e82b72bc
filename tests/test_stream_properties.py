"""Property-based suite for the streaming island (hypothesis): random
interleavings of appends, out-of-order event-time rows, and flush
punctuation must preserve the core stream invariants however they are
sequenced —

  * gathered ``seq`` strictly increasing and gap-free,
  * the ring never exceeds its capacity,
  * ``total_dropped + retained == appended``,
  * the low watermark is monotone,
  * the rolling (cumulative-ring) sum equals a recomputed sum,
  * an unsharded stream and a sharded one fed the same operation
    sequence gather bit-identically.

These are the invariants the concurrent-producer path is "correct
because of" (tests/test_stream_concurrent_producers.py races them);
here hypothesis hunts the *sequential* edge cases: batches larger than
capacity, empty batches, flushes with nothing pending, ties in ts,
eviction straddling window boundaries.

Skips cleanly when hypothesis is not installed (CI installs the
``property`` extra; the container image may not have it)."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.api import default_deployment  # noqa: E402
from repro.stream.engine import Stream  # noqa: E402

# one operation is ("append", row-values) or ("flush", to_ts | None);
# values double as both payload and (for event-time runs) jittered
# timestamps
_BATCH = st.lists(
    st.floats(min_value=0.0, max_value=400.0,
              allow_nan=False, allow_infinity=False),
    min_size=0, max_size=40)
_OPS = st.lists(
    st.one_of(
        st.tuples(st.just("append"), _BATCH),
        st.tuples(st.just("flush"),
                  st.one_of(st.none(),
                            st.floats(min_value=0.0, max_value=500.0,
                                      allow_nan=False,
                                      allow_infinity=False)))),
    min_size=1, max_size=24)

_SETTINGS = settings(max_examples=40, deadline=None,
                     suppress_health_check=[HealthCheck.too_slow])


def _apply_plain(ops, capacity):
    """Feed one op sequence into a fresh append-ordered Stream,
    checking per-op invariants; returns the stream."""
    stream = Stream("prop.plain", ("v",), capacity=capacity)
    appended = 0
    for op, arg in ops:
        if op != "append":
            continue                     # flush: event-time runs only
        counts = stream.append({"v": np.asarray(arg, np.float64)})
        appended += len(arg)
        assert counts["appended"] == len(arg)
        assert stream.num_rows <= capacity
        assert stream.total_appended == appended
        assert stream.total_dropped + stream.num_rows == appended
    return stream


@given(ops=_OPS, capacity=st.integers(min_value=1, max_value=64))
@_SETTINGS
def test_plain_stream_invariants_hold_under_any_sequence(ops, capacity):
    stream = _apply_plain(ops, capacity)
    snap = stream.snapshot()
    seqs = np.asarray(snap.columns["seq"])
    if seqs.size:
        # strictly increasing, gap-free, ending at the high-water mark
        assert (np.diff(seqs) == 1).all()
        assert seqs[-1] == stream.total_appended - 1
        assert seqs.size == stream.num_rows


@given(ops=_OPS,
       capacity=st.integers(min_value=8, max_value=128),
       shards=st.integers(min_value=2, max_value=4),
       block_rows=st.integers(min_value=1, max_value=16),
       shard_key=st.booleans())
@_SETTINGS
def test_sharded_gather_bit_identical_to_unsharded(
        ops, capacity, shards, block_rows, shard_key):
    """The same append sequence through a plain Stream and through a
    stream over several rings gathers bit-identically while no ring has
    evicted (capacity is split per shard, so this run keeps totals
    under the smallest ring)."""
    total = sum(len(arg) for op, arg in ops if op == "append")
    per_shard = -(-capacity // shards)
    if total > per_shard:
        ops = ops[:1]  # trim: eviction asymmetry is covered elsewhere
        total = sum(len(arg) for op, arg in ops if op == "append")
        if total > per_shard:
            return
    plain = Stream("prop.ref", ("v",), capacity=capacity)
    bd = default_deployment()
    sharded = bd.register_stream(
        "streamstore0", "prop.sharded", ("v",), capacity=capacity,
        shards=shards, num_engines=2, block_rows=block_rows,
        shard_key="v" if shard_key else None)
    for op, arg in ops:
        if op != "append":
            continue
        batch = np.asarray(arg, np.float64)
        plain.append({"v": batch})
        sharded.append({"v": batch})
    ref = plain.snapshot()
    got = sharded.snapshot()
    np.testing.assert_array_equal(np.asarray(ref.columns["seq"]),
                                  np.asarray(got.columns["seq"]))
    np.testing.assert_array_equal(np.asarray(ref.columns["v"]),
                                  np.asarray(got.columns["v"]))
    assert sharded.total_appended == plain.total_appended
    sharded.close()


@given(ops=_OPS, max_delay=st.floats(min_value=0.0, max_value=50.0,
                                     allow_nan=False))
@_SETTINGS
def test_event_time_invariants_hold_under_any_interleaving(ops,
                                                           max_delay):
    """Out-of-order ingest + random flush punctuation: the watermark
    never regresses, the ring is ts-sorted, seqs stay gap-free, and
    appended == flushed + pending + late."""
    stream = Stream("prop.ev", ("v",), capacity=4096,
                    ts_field="v", max_delay=max_delay)
    sent = 0
    last_wm = float("-inf")
    for op, arg in ops:
        if op == "append":
            counts = stream.append({"v": np.asarray(arg, np.float64)})
            sent += len(arg)
            assert counts["appended"] + counts["late"] == len(arg)
        else:
            stream.flush(arg)
        assert stream.watermark >= last_wm, "watermark regressed"
        last_wm = stream.watermark
        assert (stream.total_appended + stream._pending_rows
                + stream.total_late == sent)
    stream.flush()
    snap = stream.snapshot()
    seqs = np.asarray(snap.columns["seq"])
    ts = np.asarray(snap.columns["v"])
    if seqs.size:
        assert (np.diff(seqs) == 1).all()
        assert (np.diff(ts) >= 0).all(), "ring not ts-sorted"
    # every row accounted for exactly once
    assert stream.total_appended + stream.total_late == sent
    assert stream._pending_rows == 0


@given(batches=st.lists(
    st.lists(st.floats(min_value=-100, max_value=100,
                       allow_nan=False, allow_infinity=False),
             min_size=1, max_size=30),
    min_size=2, max_size=12),
    size=st.integers(min_value=2, max_value=32))
@_SETTINGS
def test_rolling_sum_equals_recomputed_sum(batches, size):
    """The O(1) cumulative-ring window aggregate must equal a cold
    recompute over the materialized window, for any batch sequence that
    leaves the window un-evicted."""
    capacity = 4096
    stream = Stream("prop.roll", ("v",), capacity=capacity)
    for batch in batches:
        stream.append({"v": np.asarray(batch, np.float64)})
    if stream.total_appended < size:
        return
    rolling = stream.window_aggregate(size, "sum", "v")
    window = np.asarray(stream.window(size).attrs["v"], np.float64)
    assert rolling == pytest.approx(float(window.sum()), abs=1e-6)
    avg = stream.window_aggregate(size, "avg", "v")
    assert avg == pytest.approx(float(window.mean()), abs=1e-6)


@given(ops=_OPS, capacity=st.integers(min_value=4, max_value=32),
       shards=st.integers(min_value=2, max_value=3))
@_SETTINGS
def test_sharded_drop_accounting_under_eviction(ops, capacity, shards):
    """Even once shard rings evict, appended == dropped + retained and
    the gathered seqs stay strictly increasing (gaps allowed: shard
    rings evict independently by design)."""
    bd = default_deployment()
    sharded = bd.register_stream(
        "streamstore0", "prop.evict", ("v",), capacity=capacity,
        shards=shards, num_engines=2, block_rows=2)
    appended = 0
    for op, arg in ops:
        if op != "append":
            continue
        sharded.append({"v": np.asarray(arg, np.float64)})
        appended += len(arg)
        assert sharded.total_appended == appended
        assert sharded.total_dropped + sharded.num_rows == appended
    seqs = np.asarray(sharded.snapshot().columns["seq"])
    if seqs.size:
        assert (np.diff(seqs) > 0).all()
        assert seqs[-1] <= sharded.total_appended - 1
    sharded.close()


# -- compiled-backend bit-identity (jit ≡ interpreted) ------------------------
# full-precision float64 payloads: the compiled path stores the ring as
# f64, computes under a scoped x64, and casts outputs to the ambient
# default dtype — any round-trip loss or reassociation shows up here as
# a bitwise mismatch
_PRECISE = st.lists(
    st.floats(min_value=-1e9, max_value=1e9,
              allow_nan=False, allow_infinity=False),
    min_size=1, max_size=60)


def _run_backend(bd, query, backend):
    """One query under one backend -> ("ok", value) | ("err", str)."""
    from repro.stream import compile as qc
    import os
    prev = os.environ.get(qc.BACKEND_ENV)
    os.environ[qc.BACKEND_ENV] = backend
    try:
        return "ok", bd.query(f"bdstream({query})").value
    except Exception as exc:                  # noqa: BLE001 — compared
        return "err", str(exc)
    finally:
        if prev is None:
            os.environ.pop(qc.BACKEND_ENV, None)
        else:
            os.environ[qc.BACKEND_ENV] = prev


def _assert_backend_parity(bd, query):
    """jit must be *bit-identical* to interpreted: same values, dtypes,
    column order — or the exact same error string."""
    ref_kind, ref = _run_backend(bd, query, "interpreter")
    got_kind, got = _run_backend(bd, query, "jit")
    assert ref_kind == got_kind, (query, ref, got)
    if ref_kind == "err":
        assert ref == got, query
        return
    r_cols = dict(getattr(ref, "columns", None) or ref.attrs)
    g_cols = dict(getattr(got, "columns", None) or got.attrs)
    assert list(r_cols) == list(g_cols), query
    for k in r_cols:
        rv, gv = np.asarray(r_cols[k]), np.asarray(g_cols[k])
        assert rv.dtype == gv.dtype, (query, k)
        np.testing.assert_array_equal(rv, gv, err_msg=f"{query} [{k}]")


@pytest.mark.parametrize("query", [
    "window(pb.s, 8)",
    "window(pb.s, 8, 3)",
    "aggregate(window(pb.s, 8), sum(v))",
    "aggregate(window(pb.s, 8), avg(v))",
    "aggregate(window(pb.s, 8), max(v))",
    "aggregate(window(pb.s, 8, 3), min(v))",
])
@given(vals=_PRECISE)
@_SETTINGS
def test_jit_backend_bit_identical_on_windows(query, vals):
    """hypothesis drives the payloads; every compiled window/aggregate
    shape must match the interpreter bit-for-bit (including the
    not-enough-rows error strings)."""
    pytest.importorskip("jax")
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "pb.s", ("v",), capacity=128)
    s.append({"v": np.asarray(vals, np.float64)})
    _assert_backend_parity(bd, query)


@given(ts=st.lists(st.floats(min_value=0.0, max_value=100.0,
                             allow_nan=False, allow_infinity=False),
                   min_size=2, max_size=50),
       tol=st.floats(min_value=0.01, max_value=5.0, allow_nan=False))
@_SETTINGS
def test_jit_join_bit_identical_under_random_event_times(ts, tol):
    """The compiled banded interval join against the interpreter, over
    arbitrary (tied, duplicated, clustered) event times — match pairs,
    ordering and the dt column must agree exactly."""
    pytest.importorskip("jax")
    bd = default_deployment()
    a = bd.register_stream("streamstore0", "pb.a", ("ts", "x"),
                           capacity=256, ts_field="ts", max_delay=0.0)
    b = bd.register_stream("streamstore0", "pb.b", ("ts", "y"),
                           capacity=256, ts_field="ts", max_delay=0.0)
    arr = np.asarray(ts, np.float64)
    a.append({"ts": arr, "x": np.arange(arr.size, dtype=np.float64)})
    b.append({"ts": arr + 0.125, "y": -np.arange(arr.size,
                                                 dtype=np.float64)})
    a.flush()
    b.flush()
    q = (f"join(ewindow(pb.a, 25, 10), ewindow(pb.b, 25, 10),"
         f" on=ts, tol={tol!r})")
    _assert_backend_parity(bd, q)
