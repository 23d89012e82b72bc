"""Hypothesis crash-point property suite: the generalized form of the
exhaustive sweep in tests/test_stream_durability.py.

One strategy draws the whole experiment — an ingest schedule (batch
sizes/values from a drawn seed), a checkpoint cadence (which appends
are followed by a blocking checkpoint), and a crash countdown ``k`` —
then the test arms ``runtime.fault`` so the k-th crash site reached
(segment-log write boundaries, checkpoint begin/promote/gc/prune)
raises ``SimulatedCrash`` mid-workload.  The property is the house
invariant of the durability layer:

  recover() ≡ some prefix of the uncrashed run, and replaying the
  remaining schedule from that prefix reconverges **bit-identically**
  to the uncrashed final state (fingerprints compare counters,
  watermarks, exact ring bytes, pending buffers, dead letters).

Shrinking note (the "custom shrinker" is strategy design, not a
Hypothesis hook): every component is ordered so default shrinking
minimizes failures — ``k`` shrinks toward 1, i.e. the EARLIEST crash
site that exhibits the bug; the schedule shrinks toward fewer/smaller
batches and zero checkpoints; the value seed toward 0.  A ``k`` larger
than the workload's crash surface simply never fires, which doubles as
the uncrashed control case (and is why ``k`` needs no upper coupling
to the drawn schedule).

``REPRO_CRASH_EXAMPLES`` scales example counts (default 40; the
acceptance bar is 200 locally, CI pins a derandomized subset).  Skips
cleanly when hypothesis is not installed (CI installs the [property]
extra).  Registered in the flake-hunter 5x matrix.
"""
import os
import shutil
import tempfile

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.runtime import fault  # noqa: E402
from repro.stream import durability as dur  # noqa: E402
from repro.stream.engine import Stream  # noqa: E402

EXAMPLES = int(os.environ.get("REPRO_CRASH_EXAMPLES", "40"))
COMMON = dict(deadline=None, derandomize=bool(os.environ.get("CI")),
              suppress_health_check=[HealthCheck.too_slow])


@pytest.fixture(autouse=True)
def _disarm():
    yield
    fault.disarm_crash_points()


@st.composite
def plain_experiment(draw):
    nops = draw(st.integers(min_value=2, max_value=7))
    sizes = draw(st.lists(st.integers(1, 40), min_size=nops,
                          max_size=nops))
    ckpt_after = sorted(draw(st.sets(st.integers(0, nops - 1),
                                     max_size=2)))
    seed = draw(st.integers(0, 2 ** 16))
    k = draw(st.integers(min_value=1, max_value=60))
    return sizes, ckpt_after, seed, k


def _values(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=n) for n in sizes]


def _run_plain(directory, batches, ckpt_after, capacity):
    s = Stream("t", ("a",), capacity)
    h = dur.attach(s, directory)
    for i, v in enumerate(batches):
        s.append({"a": v})
        if i in ckpt_after:
            h.checkpoint()
    return s


@settings(max_examples=EXAMPLES, **COMMON)
@given(exp=plain_experiment())
def test_plain_crash_recover_replay_bit_identical(exp):
    sizes, ckpt_after, seed, k = exp
    batches = _values(seed, sizes)
    capacity = 32

    ref = Stream("t", ("a",), capacity)
    snaps = [dur.fingerprint(ref)]
    for v in batches:
        ref.append({"a": v})
        snaps.append(dur.fingerprint(ref))

    d = tempfile.mkdtemp(prefix="crashprop_")
    try:
        fault.arm_crash_point("stream/*", at_hit=k)
        crashed = False
        try:
            _run_plain(d, batches, ckpt_after, capacity)
        except fault.SimulatedCrash:
            crashed = True
        report = fault.disarm_crash_points()
        assert crashed == (report["fired"] is not None)

        r = dur.recover(d)
        fp = dur.fingerprint(r.stream)
        assert fp in snaps, \
            f"fired={report['fired']}: recovered state matches no prefix"
        p = snaps.index(fp)
        if not crashed:
            assert p == len(batches)       # control case: nothing lost
        dur.attach(r.stream, d)
        for v in batches[p:]:
            r.stream.append({"a": v})
        assert dur.fingerprint(r.stream) == snaps[-1]
        # the continuation's own log is consistent too
        assert dur.fingerprint(dur.recover(d).stream) == snaps[-1]
    finally:
        fault.disarm_crash_points()
        shutil.rmtree(d, ignore_errors=True)


@st.composite
def sharded_experiment(draw):
    nshards = draw(st.integers(2, 3))
    nops = draw(st.integers(2, 6))
    sizes = draw(st.lists(st.integers(1, 30), min_size=nops,
                          max_size=nops))
    ckpt_after = sorted(draw(st.sets(st.integers(0, nops - 1),
                                     max_size=2)))
    block_rows = draw(st.sampled_from([4, 8]))
    seed = draw(st.integers(0, 2 ** 16))
    k = draw(st.integers(min_value=1, max_value=80))
    return nshards, sizes, ckpt_after, block_rows, seed, k


def _mk_sharded(nshards, block_rows):
    return Stream("w", ("a",), 128,
                  shards=[f"e{i}" for i in range(nshards)],
                  block_rows=block_rows)


@settings(max_examples=EXAMPLES, **COMMON)
@given(exp=sharded_experiment())
def test_sharded_crash_recover_replay_bit_identical(exp):
    nshards, sizes, ckpt_after, block_rows, seed, k = exp
    batches = _values(seed, sizes)

    ref = _mk_sharded(nshards, block_rows)
    snaps = [dur.fingerprint(ref)]
    for v in batches:
        ref.append({"a": v})
        snaps.append(dur.fingerprint(ref))

    d = tempfile.mkdtemp(prefix="crashprop_")
    try:
        ss = _mk_sharded(nshards, block_rows)
        h = dur.attach(ss, d)
        fault.arm_crash_point("stream/*", at_hit=k)
        try:
            for i, v in enumerate(batches):
                ss.append({"a": v})
                if i in ckpt_after:
                    h.checkpoint()
        except fault.SimulatedCrash:
            pass
        fault.disarm_crash_points()

        r = dur.recover(d)
        fp = dur.fingerprint(r.stream)
        assert fp in snaps, "recovered state matches no append prefix"
        p = snaps.index(fp)
        dur.attach(r.stream, d)
        for v in batches[p:]:
            r.stream.append({"a": v})
        assert dur.fingerprint(r.stream) == snaps[-1]
        assert dur.fingerprint(dur.recover(d).stream) == snaps[-1]
    finally:
        fault.disarm_crash_points()
        shutil.rmtree(d, ignore_errors=True)


@st.composite
def event_time_experiment(draw):
    nops = draw(st.integers(2, 6))
    sizes = draw(st.lists(st.integers(1, 16), min_size=nops,
                          max_size=nops))
    # bounded disorder: each batch's timestamps jitter within max_delay
    max_delay = draw(st.sampled_from([1.0, 4.0]))
    late_at = draw(st.one_of(st.none(), st.integers(1, nops - 1)))
    flush_end = draw(st.booleans())
    ckpt_after = sorted(draw(st.sets(st.integers(0, nops - 1),
                                     max_size=2)))
    seed = draw(st.integers(0, 2 ** 16))
    k = draw(st.integers(min_value=1, max_value=60))
    return (sizes, max_delay, late_at, flush_end, ckpt_after, seed, k)


def _event_batches(seed, sizes, max_delay, late_at):
    """Monotone-ish timestamps with jitter < max_delay, plus one
    definitely-late row injected mid-schedule when drawn."""
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for i, n in enumerate(sizes):
        ts = t + np.arange(n) + rng.uniform(0, max_delay * 0.9, n)
        t += n
        if late_at is not None and i == late_at:
            ts = np.concatenate([ts, [0.0]])     # below any watermark
        out.append({"ts": ts, "v": rng.normal(size=ts.shape[0])})
    return out, t


def _run_event(directory, batches, ckpt_after, max_delay, flush_to,
               sink):
    s = Stream("e", ("ts", "v"), 64, ts_field="ts",
               max_delay=max_delay)
    if sink:
        s._late_sink = Stream("e.__late", ("ts", "v"), 64)
    h = dur.attach(s, directory) if directory is not None else None
    for i, cols in enumerate(batches):
        s.append(cols)
        if h is not None and i in ckpt_after:
            h.checkpoint()
    if flush_to is not None:
        s.flush(flush_to)
    return s


@settings(max_examples=EXAMPLES, **COMMON)
@given(exp=event_time_experiment())
def test_event_time_crash_preserves_watermark_and_dead_letters(exp):
    sizes, max_delay, late_at, flush_end, ckpt_after, seed, k = exp
    batches, t_end = _event_batches(seed, sizes, max_delay, late_at)
    flush_to = t_end + max_delay if flush_end else None

    # reference: fingerprint after every append (and the final flush)
    ref = _run_event(None, [], [], max_delay, None, sink=True)
    snaps = [dur.fingerprint(ref)]
    for cols in batches:
        ref.append(cols)
        snaps.append(dur.fingerprint(ref))
    if flush_to is not None:
        ref.flush(flush_to)
        snaps.append(dur.fingerprint(ref))

    d = tempfile.mkdtemp(prefix="crashprop_")
    try:
        fault.arm_crash_point("stream/*", at_hit=k)
        try:
            _run_event(d, batches, ckpt_after, max_delay, flush_to,
                       sink=True)
        except fault.SimulatedCrash:
            pass
        fault.disarm_crash_points()

        r = dur.recover(d)
        fp = dur.fingerprint(r.stream)
        assert fp in snaps, "recovered state matches no prefix"
        p = snaps.index(fp)
        dur.attach(r.stream, d)
        for cols in batches[p:len(batches)]:
            r.stream.append(cols)
        if flush_to is not None:
            r.stream.flush(flush_to)
        assert dur.fingerprint(r.stream) == snaps[-1]
    finally:
        fault.disarm_crash_points()
        shutil.rmtree(d, ignore_errors=True)
