"""StreamEngine — the S-Store analog of the polystore (paper §III lists a
streaming island among BigDAWG's islands; the v0.1 release ships without
one, this module adds it).

A ``Stream`` is one logical append-only stream over a fixed set of
float64 fields.  Its rows live in one or more bounded rings; when a ring
is full its oldest rows are overwritten (drop-oldest backpressure) and
counted in ``total_dropped``.  Window views over the stream materialize
as island data-model objects:

  snapshot  — every buffered row, oldest first, as a ``dm.Table``
              (with a ``seq`` column of global sequence numbers)
  tumbling  — the most recent *complete* seq-aligned window of ``size``
              rows as a 1-D ``dm.ArrayObject`` (dims ``("tick",)``)
  sliding   — windows of ``size`` rows every ``slide`` rows over the
              buffer as a 2-D ``dm.ArrayObject`` (dims ``("window",
              "tick")``)

Materialized windows then ride the existing Migrator casts into the array
island (binary) or the relational island (staged) — see
``core/api.default_deployment``.

Scale-out (arXiv:1609.07548 §streams-across-engines): a stream with
several rings partitions its rows across them — one ring per
StreamEngine placement, scatter appends, seq-ordered merged reads — so
the BQL ops stay ring-transparent.  Rings are *live-migratable* between
StreamEngines (the Migrator's ``stream`` route moves data + seq
watermark + drop counters) without interrupting standing queries.

Multi-producer ingest (arXiv:1905.10336's observation that polystore
throughput dies at serialized ingest boundaries): appends do not
serialize on one coordinator lock.  A producer atomically *reserves* a
contiguous block of global sequence numbers under a micro-lock (counter
bumps only — no ring work ever runs inside it), stages its rows into
per-ring payloads on its own thread, and publishes each payload through
that ring's **ordered committer**, which admits blocks strictly in
reservation order — so every ring stays seq-sorted and gathers, rolling
sums, watermark flushes and drop accounting are bit-identical to a
serial feed.  Reads see the *committed frontier*: a seq is visible only
once every block below it has fully published, so a gather can never
observe a half-written batch.  ``Stream.producer()`` hands out
per-producer handles and ``ingest_concurrency()`` reports the
reservation/contention counters (surfaced via Monitor/admin.status()).
Event-time streams keep their insertion buffer serialized — there the
global seq is *reserved at flush time* (ts order), and concurrent
producers contend only for the cheap buffer parking.

Event time (arXiv:1609.07548 makes S-Store the polystore's time-ordered
engine): a stream declared with ``ts_field`` accepts bounded out-of-order
ingest.  Arriving rows park in an insertion buffer until the stream's
**low watermark** — ``max(ts seen) - max_delay``, and the *minimum across
rings* for key-hashed streams — passes them; they are then flushed into
the rings in timestamp order, with the global ``seq`` assigned *at flush
time*, so seq order and event-time order coincide and every seq-aligned
op keeps working.  Rows arriving below the watermark are **late**:
dropped and counted (``total_late``), never silently reordered.
``ewindow(span[, slide])`` is the event-time window view, closed only
once the watermark passes its end.  Streams without ``ts_field`` keep
the exact append-ordered semantics.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
import operator
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import (Any, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax.numpy as jnp
import numpy as np

from repro.core import datamodel as dm
from repro.core.engines import ENGINE_KINDS, Engine
from repro.core.executor import DataUnavailableException
from repro.obs import metrics, trace

# reserved per-row field carrying the logical stream's global sequence
# number inside every ring (float64 is exact for seq < 2**53)
SEQ_FIELD = "__seq"

# aggregates that decompose into per-ring partials / rolling sums
_COMBINABLE_AGGS = ("count", "sum", "avg", "min", "max")


def to_device(values) -> jnp.ndarray:
    """``jnp.asarray(values)``, counting the bytes that land on the
    device in ``repro_stream_h2d_bytes_total``: every host array a
    stream query hands to the device goes through here."""
    arr = jnp.asarray(values)
    metrics.counter("repro_stream_h2d_bytes_total",
                    "bytes the stream path copied from host arrays to "
                    "the device").inc(arr.nbytes)
    return arr


# -- view errors: one wording for the interpreted and the compiled path -------
def _tumbling_start(name: str, size: int, total: int) -> int:
    """First seq of the latest complete tumbling window of ``size`` rows
    below the frontier ``total``; raises while there is none."""
    k = total // size - 1
    if k < 0:
        raise StreamException(
            f"stream {name!r}: no complete window of size {size} yet "
            f"({total} rows)")
    return k * size


def _window_evicted(name: str, s: int, e: int,
                    rows: int) -> StreamException:
    return StreamException(
        f"stream {name!r}: window [{s},{e}) already evicted (rings "
        f"retain {rows}/{e - s} rows)")


def _too_few_rows(name: str, count: int, size: int) -> StreamException:
    return StreamException(
        f"stream {name!r}: {count} contiguous rows < window size {size}")


def _ewindow_evicted(name: str, start: float, end: float,
                     evicted: float) -> StreamException:
    return StreamException(
        f"stream {name!r}: ewindow [{start},{end}) already evicted "
        f"(rows up to ts {evicted} overwritten)")


def _latest_closed_ewindow(stream, span: float,
                           slide: Optional[float]) -> Tuple[float, float]:
    """(start, end) of the latest *closed* event-time window of ``stream``
    — windows are aligned to multiples of ``slide`` (default ``span``) on
    the ts axis, and closed means the low watermark has passed the end.
    Raises while no window is closed or the stream has no event-time
    field."""
    span = float(span)
    step = float(slide) if slide is not None else span
    if span <= 0 or step <= 0:
        raise StreamException(
            f"stream {stream.name!r}: ewindow span/slide must be "
            f"positive, got ({span}, {step})")
    if stream.ts_field is None:
        raise StreamException(
            f"stream {stream.name!r} has no event-time field "
            f"(declare it with ts_field=...)")
    wm = stream.watermark
    if wm == float("-inf"):
        raise StreamException(
            f"stream {stream.name!r}: watermark has not started, "
            f"no closed ewindow yet")
    k = math.floor((wm - span) / step)
    start = k * step
    while start + span > wm:                  # float-rounding guard
        k -= 1
        start = k * step
    if start + span <= stream.min_ts_seen:
        # the window axis is unbounded, but a window that ends before
        # the first row ever seen says nothing about the stream yet
        raise StreamException(
            f"stream {stream.name!r}: no closed ewindow covering data "
            f"yet (watermark {wm}, first ts {stream.min_ts_seen})")
    return start, start + span


def _key_owners(values: np.ndarray, num_rings: int) -> np.ndarray:
    """Ring owner of each row under key-hash partitioning:
    ``floor(|v|) mod N``.  Non-finite key values (NaN/±inf — missing
    vitals, sensor saturation) route deterministically to ring 0
    instead of through the C-undefined float->int64 cast."""
    return np.floor(np.abs(np.nan_to_num(
        values, nan=0.0, posinf=0.0, neginf=0.0))
    ).astype(np.int64) % num_rings


def _recent_rate(append_times: "collections.deque[Tuple[float, int]]"
                 ) -> float:
    """Rows/second over the recent (wall_time, rows) append history —
    0.0 with fewer than two appends (caller holds the owning lock)."""
    if len(append_times) < 2:
        return 0.0
    t0, _ = append_times[0]
    t1, _ = append_times[-1]
    if t1 <= t0:
        return 0.0
    rows = sum(n for _, n in list(append_times)[1:])
    return rows / (t1 - t0)


def _merge_runs(runs: List[Dict[str, np.ndarray]]
                ) -> Dict[str, np.ndarray]:
    """Merge seq-sorted runs (one per ring) into one run in global seq
    order.  The merge of one run is that run."""
    if len(runs) == 1:
        return runs[0]
    cat = {f: np.concatenate([r[f] for r in runs]) for f in runs[0]}
    order = np.argsort(cat[SEQ_FIELD], kind="stable")
    return {f: v[order] for f, v in cat.items()}


def _ring_name(name: str, idx: int, num_rings: int) -> str:
    """A ring's engine-object name: the stream's own for a one-ring
    stream, ``{name}@shard{i}`` otherwise."""
    return name if num_rings == 1 else f"{name}@shard{idx}"


class StreamException(DataUnavailableException):
    """Data-dependent streaming error (window not complete / evicted,
    schema mismatch on append).  Subclasses the core's transient marker
    so cached plans survive it."""


class _OrderedCommitter:
    """FIFO block publisher for one commit lane (one ring of a stream).

    Tickets are issued in seq-reservation order — the caller issues
    while holding its reservation micro-lock, so ticket order == global
    seq order on this lane.  ``commit(ticket, fn)`` blocks until every
    earlier ticket has published, runs ``fn`` (the ring write), then
    releases the next block: the lane's ring receives blocks strictly
    in seq order even when producers finish staging out of order.

    Because tickets are issued under ONE micro-lock, the wait-for graph
    across lanes always follows global reservation order (an earlier
    producer never waits on a later one), so committing multiple lanes
    in any per-producer order cannot deadlock.

    ``pause()`` is the live-migration barrier: it drains every already-
    issued ticket (in-flight blocks publish to the old ring) and holds
    later tickets back until ``resume()`` — those blocks carry over to
    whatever object the commit closure resolves after the swap.

    Stall stealing: a producer that reserved a ticket and then died
    (hard-killed thread, crashed process stage) would otherwise park
    every later ticket on its lane forever.  Any waiter (commit,
    quiesce, pause) that sees **zero lane progress** for
    ``stall_timeout`` seconds steals the head ticket if its owner never
    entered ``commit()`` — the lane advances over the hole and the
    stolen ticket's ``commit``, should the owner revive, raises instead
    of double-advancing the lane.  Tickets whose owners are alive
    (waiting, or running their ring write) are never stolen.
    ``REPRO_COMMIT_STALL_TIMEOUT`` (seconds, default 5.0) tunes it;
    0 disables stealing."""

    def __init__(self) -> None:
        self._cond = threading.Condition(threading.Lock())
        self._next_ticket = 0
        self._committed = 0
        self._pause_at: Optional[int] = None
        self._entered: set = set()     # tickets with a live owner inside commit
        self._stolen: set = set()      # tickets advanced over after a stall
        self.waits = 0             # commits that had to block (contention)
        self.steals = 0            # tickets stolen from presumed-dead owners
        self.stall_timeout = float(os.environ.get(
            "REPRO_COMMIT_STALL_TIMEOUT", "5.0"))

    def issue(self) -> int:
        with self._cond:
            ticket = self._next_ticket
            self._next_ticket += 1
            return ticket

    def _turn(self, ticket: int) -> bool:
        return (self._committed == ticket
                and (self._pause_at is None or ticket < self._pause_at))

    def _wait_or_steal_locked(self, done, limit: int) -> None:
        """Wait (holding the condition) until ``done()``; whenever a
        full stall interval passes with no lane progress at all, steal
        never-entered tickets from the head up to ``limit``.  With
        stealing disabled this is a plain ``wait_for``."""
        timeout = self.stall_timeout if self.stall_timeout > 0 else None
        while not done():
            if not self._cond.wait(timeout=timeout):
                # a timeout means no notify — so no commit on this lane
                # — for stall_timeout seconds: the head owner is dead
                # or wedged; steal it if it never entered commit()
                self._steal_stalled_locked(limit)

    def _steal_stalled_locked(self, limit: int) -> None:
        stole = False
        while (self._committed < limit
               and self._committed < self._next_ticket
               and (self._pause_at is None
                    or self._committed < self._pause_at)
               and self._committed not in self._entered):
            self._stolen.add(self._committed)
            self._committed += 1
            self.steals += 1
            stole = True
        if stole:
            self._cond.notify_all()

    def commit(self, ticket: int, fn):
        """Publish ticket's block: wait for its turn, run ``fn``, release
        the next.  ``fn``'s return value is passed through; the lane
        advances even when ``fn`` raises (a poisoned block must not wedge
        every later producer forever).  Raises StreamException — without
        running ``fn`` or advancing the lane — when the ticket was
        stolen after a stall (the lane already moved past it).

        ``fn`` runs OUTSIDE the condition lock: once it is ticket's turn
        no other commit can run on this lane until ``_committed``
        advances (in the finally), so mutual exclusion holds — and
        ``issue()`` (called under the owner's reservation micro-lock)
        never blocks behind an in-progress ring write, keeping the
        reservation path counter-bumps-only for real."""
        with self._cond:
            if ticket in self._stolen:
                self._stolen.discard(ticket)
                raise StreamException(
                    f"commit ticket {ticket} was stolen after a "
                    f"{self.stall_timeout:g}s stall (producer presumed "
                    f"dead); its block is a permanent hole")
            self._entered.add(ticket)
            if not self._turn(ticket):
                self.waits += 1
                self._wait_or_steal_locked(
                    lambda: self._turn(ticket), ticket)
        try:
            return fn()
        finally:
            with self._cond:
                self._entered.discard(ticket)
                self._committed += 1
                self._cond.notify_all()

    def consumed(self, ticket: int) -> bool:
        """True once the lane moved past ``ticket`` (committed or
        stolen)."""
        with self._cond:
            return self._committed > ticket

    def was_stolen(self, ticket: int) -> bool:
        with self._cond:
            return ticket in self._stolen

    def quiesce(self) -> None:
        """Drain: wait until every ticket issued so far has committed
        (no pause — new tickets keep flowing afterwards; tickets of
        dead producers are stolen rather than waited on forever)."""
        with self._cond:
            barrier = self._next_ticket
            self._wait_or_steal_locked(
                lambda: self._committed >= barrier, barrier)

    def pause(self) -> None:
        """Drain issued tickets and hold later ones until resume()."""
        with self._cond:
            assert self._pause_at is None, "committer already paused"
            self._pause_at = self._next_ticket
            self._wait_or_steal_locked(
                lambda: self._committed >= self._pause_at,
                self._pause_at)

    def resume(self) -> None:
        with self._cond:
            self._pause_at = None
            self._cond.notify_all()

    @property
    def in_flight(self) -> int:
        with self._cond:
            return self._next_ticket - self._committed


class StreamProducer:
    """One producer's handle onto a stream (``stream.producer()``).

    ``append`` delegates to the stream's reservation path — the handle
    adds no locking of its own — while tracking per-producer counts;
    the stream tracks how many handles are open at once
    (``ingest_concurrency()["producers_open"/"producers_peak"]``).
    Context manager; ``close()`` is idempotent."""

    def __init__(self, stream, name: Optional[str] = None) -> None:
        self.stream = stream
        serial = stream._producer_opened()
        self.name = name or f"{stream.name}#p{serial}"
        self.batches = 0
        self.rows = 0
        self.dropped = 0
        self._closed = False

    def append(self, rows: Dict[str, Iterable[float]]) -> Dict[str, int]:
        counts = self.stream.append(rows)
        self.batches += 1
        self.rows += counts["appended"]
        self.dropped += counts.get("dropped", 0)
        return counts

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.stream._producer_closed()

    def __enter__(self) -> "StreamProducer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _RingExport(NamedTuple):
    """One point-in-time export of a ring for the compiled plans."""
    count: int                 # rows in the ring
    a: int                     # ordered offsets [a, b) of the selected rows
    b: int
    first: Optional[float]     # seq of row ``a`` (None when a == b)
    rows: np.ndarray           # (F, capacity) oldest first, zero-padded


class _Ring:
    """One ring of a stream: a bounded buffer of float64 columns (the
    stream's fields plus the reserved seq column), always sorted on seq
    — and, for an event-time stream, on its ts field.  When full, the
    oldest rows are overwritten and counted in ``total_dropped``.

    The ring owns its columns, the lazily built cumulative rings behind
    O(1) range sums (re-anchored once per ring generation), the
    eviction boundary on its sorted field and the lock guarding them.
    Nothing outside this class touches those: a multi-ring read holds
    ``_Ring.cut`` and calls the ``*_locked`` methods, everything else
    takes the lock itself."""

    def __init__(self, name: str, fields: Sequence[str], capacity: int,
                 rolling: bool = True,
                 evict_field: Optional[str] = None) -> None:
        self.name = name
        self.fields: Tuple[str, ...] = tuple(fields)
        self.capacity = int(capacity)
        self.rolling = bool(rolling)
        # the ring stays sorted on this field (an event-time stream's
        # ts): track the newest evicted value so closed windows that
        # lost rows to ring overflow raise instead of returning silent
        # partials
        self.evict_field = evict_field
        self.evicted_ts = float("-inf")
        self._cols = {f: np.zeros(self.capacity, np.float64)
                      for f in self.fields}
        # rolling-sum support: _cum[f][pos] is the running total of field
        # f over the buffered rows up to and including pos, so a sum over
        # any buffered range is one subtraction (see _range_sum_locked).
        # Built lazily on a field's first rolling aggregate — pure
        # ingest streams never pay the memory or the per-append cumsum —
        # and ``rolling=False`` disables them outright.
        self._cum: Dict[str, np.ndarray] = {}
        self._running: Dict[str, float] = {}
        self._next = 0                    # ring write position
        self._count = 0                   # valid rows in the buffer
        self.total_appended = 0           # rows ever written here
        self.total_dropped = 0            # rows overwritten
        self._lock = threading.Lock()

    @staticmethod
    @contextlib.contextmanager
    def cut(rings: Sequence["_Ring"]):
        """Hold every ring's lock at once (acquired in index order) so a
        read across rings is a point-in-time cut: a commit landing on
        one ring mid-read cannot evict rows from a ring the reader has
        not reached yet.  Writers take one ring lock at a time and
        never while holding another, so the ordered sweep cannot
        deadlock."""
        with contextlib.ExitStack() as stack:
            for ring in rings:
                stack.enter_context(ring._lock)
            yield

    # -- write ----------------------------------------------------------------
    def write(self, cols: Dict[str, np.ndarray]) -> int:
        """Append a seq-ordered block (``cols`` carries every ring
        field); returns the rows it overwrote."""
        n = cols[SEQ_FIELD].shape[0]
        with self._lock:
            return self._ingest_locked(cols, n)

    def _ingest_locked(self, cols: Dict[str, np.ndarray], n: int) -> int:
        dropped = max(0, self._count + n - self.capacity)
        if dropped and self.evict_field is not None:
            # the ring is sorted on the evict field, and so is the
            # concatenation of (buffered rows, this batch) — the newest
            # evicted value is at concat offset dropped-1
            f = self.evict_field
            if dropped <= self._count:
                boundary = float(self._ordered(f)[dropped - 1])
            else:
                boundary = float(cols[f][dropped - self._count - 1])
            self.evicted_ts = max(self.evicted_ts, boundary)
        for f in self.fields:
            src = cols[f][-self.capacity:]        # keep only the tail
            cum = None
            if f in self._cum:
                cum = np.cumsum(src) + self._running[f]
                self._running[f] = float(cum[-1])
            m = src.shape[0]
            end = self._next + m
            if end <= self.capacity:
                self._cols[f][self._next:end] = src
                if cum is not None:
                    self._cum[f][self._next:end] = cum
            else:
                first = self.capacity - self._next
                self._cols[f][self._next:] = src[:first]
                self._cols[f][:end % self.capacity] = src[first:]
                if cum is not None:
                    self._cum[f][self._next:] = cum[:first]
                    self._cum[f][:end % self.capacity] = cum[first:]
        self._next = (self._next + min(n, self.capacity)) % self.capacity
        self._count = min(self.capacity, self._count + n)
        prev_total = self.total_appended
        self.total_appended += n
        self.total_dropped += dropped
        # re-anchor the cumulative rings once per ring generation
        # (amortized O(1)/row): without this the running totals grow
        # for the stream's lifetime and the O(1) range-sum subtraction
        # loses float64 precision for large-magnitude fields (e.g.
        # epoch-millisecond timestamps) under steady small batches
        if (self._cum and self.total_appended // self.capacity
                != prev_total // self.capacity):
            self._reanchor_cums_locked()
        return dropped

    def _reanchor_cums_locked(self) -> None:
        """Rewrite every cumulative slot as a prefix sum over the
        *buffered* rows only (base 0 at the oldest row).  All slots are
        rewritten in one epoch, so range-sum differences stay exact, and
        the running totals stay bounded by ~capacity x max|value|."""
        idx = self._index(0, self._count)
        for f in self._cum:
            cum = np.cumsum(self._cols[f][idx])
            self._cum[f][idx] = cum
            self._running[f] = float(cum[-1]) if self._count else 0.0

    # -- reads (caller holds the lock) ----------------------------------------
    def _pos(self, offset: int) -> int:
        """Ring position of the ``offset``-th oldest buffered row."""
        return (self._next - self._count + offset) % self.capacity

    def _index(self, a: int, b: int) -> np.ndarray:
        """Ring positions of the buffered rows at ordered offsets
        [a, b) (offset 0 = oldest)."""
        return (self._pos(0) + np.arange(a, b)) % self.capacity

    def _ordered(self, field: str) -> np.ndarray:
        """Buffered values oldest-first."""
        return self._cols[field][self._index(0, self._count)]

    def _bounds_locked(self, field: str, lo: float, hi: float
                       ) -> Tuple[int, int]:
        """Ordered offsets [a, b) of buffered rows whose ``field`` value
        lies in ``[lo, hi)`` (the field is non-decreasing in ring
        order).  Binary search over the ring's two contiguous segments
        — no materialization."""
        start = self._pos(0)
        end = start + self._count
        col = self._cols[field]
        if end <= self.capacity:
            seg = col[start:end]
            return (int(np.searchsorted(seg, lo)),
                    int(np.searchsorted(seg, hi)))
        older, newer = col[start:], col[:end % self.capacity]
        n1 = older.shape[0]
        fa, fb = np.searchsorted(older, lo), np.searchsorted(older, hi)
        a = int(fa) if fa < n1 else n1 + int(np.searchsorted(newer, lo))
        b = int(fb) if fb < n1 else n1 + int(np.searchsorted(newer, hi))
        return a, b

    def _ensure_cum_locked(self, field: str) -> bool:
        """Build the field's cumulative ring on first use.  Returns False
        when rolling is disabled."""
        if field in self._cum:
            return True
        if not self.rolling or field == SEQ_FIELD:
            return False
        self._cum[field] = np.zeros(self.capacity, np.float64)
        self._running[field] = 0.0
        self._reanchor_cums_locked()
        return True

    def _range_sum_locked(self, field: str, a: int, b: int) -> float:
        """Sum of the buffered rows at ordered offsets [a, b): O(1) via
        the cumulative ring."""
        if not self._ensure_cum_locked(field):      # rolling=False
            return float(self._cols[field][self._index(a, b)].sum())
        hi = float(self._cum[field][self._pos(b - 1)])
        if a > 0:
            lo = float(self._cum[field][self._pos(a - 1)])
        else:
            p = self._pos(0)
            lo = float(self._cum[field][p]) - float(self._cols[field][p])
        return hi - lo

    def rows_locked(self, field: str, lo: float, hi: float,
                    fields: Sequence[str]) -> Dict[str, np.ndarray]:
        """Copies of ``fields`` for the rows whose ``field`` lies in
        [lo, hi), oldest first (caller holds the cut)."""
        idx = self._index(*self._bounds_locked(field, lo, hi))
        return {f: self._cols[f][idx] for f in fields}

    def partial_locked(self, fn: str, field: str, lo: float,
                       hi: float) -> Tuple[float, int]:
        """(``fn`` over ``field``, row count) for the rows whose seq lies
        in [lo, hi) — count/sum/avg in O(1) through the cumulative ring,
        min/max over the slice (caller holds the cut)."""
        a, b = self._bounds_locked(SEQ_FIELD, lo, hi)
        if b <= a:
            return 0.0, 0
        if fn == "count":
            return float(b - a), b - a
        if fn in ("sum", "avg"):
            return self._range_sum_locked(field, a, b), b - a
        sl = self._cols[field][self._index(a, b)]
        return float(sl.min() if fn == "min" else sl.max()), b - a

    # -- self-locking reads ---------------------------------------------------
    def export(self, fields: Sequence[str], field: str = SEQ_FIELD,
               lo: float = -math.inf, hi: float = math.inf) -> _RingExport:
        """Every buffered row of ``fields`` as one (F, capacity) float64
        array, oldest first and zero-padded, with the ordered offsets of
        the rows whose ``field`` lies in [lo, hi) — one point-in-time
        export for the compiled plans."""
        with self._lock:
            a, b = self._bounds_locked(field, lo, hi)
            count = self._count
            rows = np.zeros((len(fields), self.capacity), np.float64)
            for j, f in enumerate(fields):
                rows[j, :count] = self._ordered(f)
            first = (float(self._cols[SEQ_FIELD][self._pos(a)])
                     if b > a else None)
        return _RingExport(count, a, b, first, rows)

    @property
    def num_rows(self) -> int:
        with self._lock:
            return self._count

    def nbytes(self) -> int:
        return int(sum(v.nbytes for v in self._cols.values()))

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"rows": self._count, "capacity": self.capacity,
                    "appended": self.total_appended,
                    "dropped": self.total_dropped}

    # -- state ----------------------------------------------------------------
    def export_state(self) -> Dict[str, Any]:
        """Deep copy of the ring: its buffered rows and cumulative
        slots oldest first, running totals, counters and eviction
        boundary.  The caller keeps writers off the ring (a paused lane,
        or the stream's lock for event-time flushes)."""
        with self._lock:
            idx = self._index(0, self._count)
            return {"name": self.name, "fields": self.fields,
                    "capacity": self.capacity, "rolling": self.rolling,
                    "cols": {f: v[idx] for f, v in self._cols.items()},
                    "cum": {f: v[idx] for f, v in self._cum.items()},
                    "running": dict(self._running),
                    "total_appended": self.total_appended,
                    "total_dropped": self.total_dropped,
                    "evict_field": self.evict_field,
                    "evicted_ts": self.evicted_ts}

    @classmethod
    def from_state(cls, state: Dict[str, Any]) -> "_Ring":
        ring = cls(state["name"], state["fields"], state["capacity"],
                   rolling=state["rolling"],
                   evict_field=state["evict_field"])
        count = state["cols"][SEQ_FIELD].shape[0]
        for f, v in state["cols"].items():
            ring._cols[f][:count] = v
        for f, v in state["cum"].items():
            ring._cum[f] = np.zeros(ring.capacity, np.float64)
            ring._cum[f][:count] = v
        ring._running = dict(state["running"])
        ring._count, ring._next = count, count % ring.capacity
        ring.total_appended = int(state["total_appended"])
        ring.total_dropped = int(state["total_dropped"])
        ring.evicted_ts = float(state["evicted_ts"])
        return ring


class Stream:
    """One logical append-only stream over a fixed set of float64
    fields, stored in one or more rings.

    A **ring** (``_Ring``) is a bounded, drop-oldest buffer of float64
    columns.  Every ring stores the reserved ``__seq`` column — each
    row's global sequence number — and stays sorted on it (and on the ts
    field of an event-time stream), so any seq or ts range is found in
    a ring by binary search.  An unsharded stream is a stream with one
    ring.  With ``shards`` (the engine of each ring) rows partition
    across several rings of ``capacity`` rows each: round-robin over
    contiguous seq blocks of ``block_rows`` (balanced; a batch splits
    into zero-copy views) or, with ``shard_key``, by hash of a field's
    value (``floor(|v|) mod N`` — the skew-prone placement the rebalance
    hook exists for).  Each ring is then registered on its engine as
    ``{name}@shard{i}``, live-migratable there (``migrate_shard``), and
    this handle on every participating engine.  Rings evict
    independently, so skewed key traffic can evict a hot ring's rows
    earlier than one big ring would have (seq gaps in snapshots,
    tumbling windows raise).

    The coordinator (this object) owns everything above the rings:

      * ingest — the reservation micro-lock (seq counter plus one
        ordered-commit ticket per touched ring, no ring work), the
        partition into per-ring payloads on the producer's thread, the
        per-ring ordered commit lanes and the committed frontier
        (``total_appended``: every seq below it has fully published,
        and reads see none above it, so never half a batch);
      * event time — the insertion buffer, late classification and the
        watermark (global max timestamp, or the minimum across rings of
        a key-hashed stream, with idle rings excluded);
      * views — snapshot, windows and ewindows as seq-ordered merges of
        one sorted run per ring, and tumbling-window aggregates combined
        from per-ring partials, memoized per window index;
      * rate, stats, export/clone/checkpoint and live ring moves.

    With one ring the partition is the whole batch, the merge of the one
    run is that run, and an aggregate's one partial is the answer.
    """

    # fan the per-ring writes of one batch out to a thread pool only when
    # the batch is big enough for numpy to dominate (below this the
    # pool overhead wins)
    PARALLEL_APPEND_MIN_ROWS = 2048

    def __init__(self, name: str, fields: Sequence[str],
                 capacity: int = 4096, rolling: bool = True,
                 ts_field: Optional[str] = None,
                 max_delay: float = 0.0,
                 idle_timeout: Optional[float] = None,
                 shards: Optional[Sequence[Optional[str]]] = None,
                 shard_key: Optional[str] = None,
                 block_rows: int = 64) -> None:
        assert fields, "a stream needs at least one field"
        assert SEQ_FIELD not in fields, SEQ_FIELD
        assert capacity > 0, "capacity must be positive"
        assert ts_field is None or ts_field in fields, ts_field
        assert shard_key is None or shard_key in fields, shard_key
        assert max_delay >= 0.0 and block_rows > 0
        assert idle_timeout is None or idle_timeout > 0
        self.name = name
        self.fields: Tuple[str, ...] = tuple(fields)
        self.rolling = bool(rolling)
        self.ts_field = ts_field
        self.max_delay = float(max_delay)
        self.idle_timeout = idle_timeout
        self.shard_key = shard_key
        self.block_rows = int(block_rows)
        # -- the rings, and the engine each lives on (None: one ring,
        # held by the stream object itself)
        self._engines: List[Optional[str]] = list(shards or [None])
        n = len(self._engines)
        self._rings = [_Ring(_ring_name(name, i, n),
                             self.fields + (SEQ_FIELD,), capacity,
                             rolling=rolling, evict_field=ts_field)
                       for i in range(n)]
        # -- multi-producer ingest: ``reserved`` is the next global seq
        # to hand out, ``total_appended`` the committed frontier; the
        # block ledgers behind the frontier let it skip a block whose
        # producer died mid-stage once its stolen tickets prove it can
        # never complete (a permanent hole, as for a staging failure)
        self._reserve_lock = threading.Lock()   # seq/ticket micro-lock
        self.reserved = 0
        self.total_appended = 0
        self.blocks_reserved = 0   # reserve calls (flushes, for ts streams)
        self.rows_reserved = 0     # rows covered by those reservations
        self.blocks_abandoned = 0
        self.producers_open = 0
        self.producers_peak = 0
        self._producer_serial = 0
        self._committers = [_OrderedCommitter() for _ in self._rings]
        self._frontier = threading.Condition(threading.Lock())
        self._finished: Dict[int, int] = {}      # block start -> rows
        self._pending_blocks: Dict[int, Tuple[int, Dict[int, int]]] = {}
        # the scatter pool serves ONE producer at a time (pool tasks
        # block on commit order; sharing it across producers could queue
        # an earlier producer's ring write behind a later producer's
        # waiting task — a deadlock); contenders commit inline
        self._pool_gate = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        # -- event time: rows buffer until the low watermark passes them,
        # then flush ts-ordered with seqs assigned in that order
        self.watermark = float("-inf")    # low watermark (flush boundary)
        self.max_ts_seen = float("-inf")
        self.min_ts_seen = float("inf")   # first event ever accepted
        self.total_late = 0               # rows arriving below the watermark
        self._pending: List[Dict[str, np.ndarray]] = []   # insertion buffer
        self._pending_rows = 0
        # per-ring max ts / last arrival (key-hashed streams: the
        # watermark basis is the minimum over rings with data, and a
        # ring quiet for ``idle_timeout`` seconds stops holding it back)
        self._shard_max_ts = [float("-inf")] * n
        self._shard_last_arrival: List[Optional[float]] = [None] * n
        self._last_arrival: Optional[float] = None
        self._now = time.monotonic        # injectable for tests
        # -- views
        self._rate_lock = threading.Lock()        # guards _append_times
        self._append_times: "collections.deque[Tuple[float, int]]" = \
            collections.deque(maxlen=64)
        # (fn, field, size) -> (window index k, value): repeated ticks over
        # the same complete tumbling window skip recompute entirely
        self._agg_cache: Dict[Tuple[str, str, int], Tuple[int, float]] = {}
        self.agg_cache_hits = 0
        self.agg_computes = 0
        self.migrations = 0               # live ring moves (rebalances)
        self._lock = threading.RLock()
        # -- durability (opt-in, see repro.stream.durability): the
        # write-behind segment-log hook and the late-row dead-letter
        # sink.  Both None by default — the hot path pays one attribute
        # check per batch and nothing else.
        self._durable = None
        self._late_sink: Optional["Stream"] = None
        # registration spec (repro.stream.spec.StreamSpec), set by
        # BigDawg._register_spec / recover_stream; None when the stream
        # was built directly
        self.spec = None

    # -- topology -------------------------------------------------------------
    @property
    def num_shards(self) -> int:
        return len(self._rings)

    @property
    def rings(self) -> Tuple[_Ring, ...]:
        return tuple(self._rings)

    @property
    def home_engine(self) -> Optional[str]:
        """Engine anchoring ring 0 — the Planner's canonical placement
        for merged reads (all placements are equivalent; pinning one
        keeps plan enumeration from exploding with engine count).  None
        for a one-ring stream, which lives on one engine only."""
        with self._lock:
            return self._engines[0]

    def shard_name(self, idx: int) -> str:
        return _ring_name(self.name, idx, len(self._rings))

    def shard_engines(self) -> List[Optional[str]]:
        with self._lock:
            return list(self._engines)

    @property
    def capacity(self) -> int:
        return sum(r.capacity for r in self._rings)

    @property
    def total_dropped(self) -> int:
        return sum(r.total_dropped for r in self._rings)

    @property
    def num_rows(self) -> int:
        return sum(r.num_rows for r in self._rings)

    @property
    def evicted_ts(self) -> float:
        """Event-time eviction horizon: rows at or below this ts have
        been overwritten in some ring."""
        return max(r.evicted_ts for r in self._rings)

    def nbytes(self) -> int:
        # the rings of a multi-ring stream are engine objects of their
        # own and counted there; a one-ring stream holds its ring
        return self._rings[0].nbytes() if len(self._rings) == 1 else 0

    # -- producers ------------------------------------------------------------
    def producer(self, name: Optional[str] = None) -> "StreamProducer":
        """A handle for one ingest thread; see StreamProducer."""
        return StreamProducer(self, name)

    def _producer_opened(self) -> int:
        with self._reserve_lock:
            self.producers_open += 1
            self.producers_peak = max(self.producers_peak,
                                      self.producers_open)
            self._producer_serial += 1
            return self._producer_serial

    def _producer_closed(self) -> None:
        with self._reserve_lock:
            self.producers_open -= 1

    def ingest_concurrency(self) -> Dict[str, int]:
        """Reservation/contention counters of the multi-producer ingest
        path: how many producer handles are (were) open, how many seq
        blocks/rows have been reserved, how many are reserved but not
        yet published (``in_flight_rows``; event-time streams reserve at
        flush, so theirs is always 0), how many commits had to wait for
        an earlier block (``commit_waits`` — the contention signal; 0
        under a single producer), how many tickets were stolen from
        stalled producers (``commit_steals``) and how many blocks the
        frontier skipped for good (``blocks_abandoned``)."""
        return {"producers_open": self.producers_open,
                "producers_peak": self.producers_peak,
                "blocks_reserved": self.blocks_reserved,
                "rows_reserved": self.rows_reserved,
                "in_flight_rows": self.reserved - self.total_appended,
                "commit_waits": sum(c.waits for c in self._committers),
                "commit_steals": sum(c.steals for c in self._committers),
                "blocks_abandoned": self.blocks_abandoned}

    # -- ingest ---------------------------------------------------------------
    def append(self, rows: Dict[str, Iterable[float]]) -> Dict[str, int]:
        """Append a batch of rows (column dict); returns counts.

        Rows beyond a ring's capacity overwrite its oldest rows; the
        overwritten count is the batch's ``dropped`` (backpressure is
        drop-oldest, never blocking the producer).  Concurrent
        producers are safe (see ``_append_seq``); event-time streams
        park rows in the insertion buffer (``_append_event_time``)."""
        if set(rows) != set(self.fields):
            raise StreamException(
                f"stream {self.name!r} fields {self.fields} != "
                f"appended fields {tuple(rows)}")
        cols = {f: np.asarray(rows[f], np.float64).reshape(-1)
                for f in self.fields}
        n = cols[self.fields[0]].shape[0]
        if any(v.shape[0] != n for v in cols.values()):
            raise StreamException("ragged append batch")
        if n == 0:
            counts = {"appended": 0, "dropped": 0, "rows": self.num_rows}
            if self.ts_field is not None:
                counts.update(late=0, flushed=0, pending=self._pending_rows)
            return counts
        with trace.span("stream/append", stream=self.name, rows=n,
                        shards=len(self._rings)):
            if self.ts_field is not None:
                return self._append_event_time(cols, n)
            return self._append_seq(cols, n)

    def _note_append(self, rows: int) -> None:
        with self._rate_lock:
            self._append_times.append((time.monotonic(), rows))

    def _append_seq(self, cols: Dict[str, np.ndarray],
                    n: int) -> Dict[str, int]:
        """Reserve-stage-publish for validated float64 columns.  The
        producer reserves the global seq block [t, t+n) under the
        reservation micro-lock (counter bumps and per-ring commit
        tickets, never ring work), partitions its rows into per-ring
        payloads on its own thread, and publishes each payload through
        that ring's ordered committer — so concurrent producers overlap
        all staging work and serialize only the per-ring writes, in seq
        order, keeping every ring seq-sorted and reads bit-identical to
        a serial feed."""
        nsh = len(self._rings)
        owner = present = None
        if self.shard_key is not None:
            # key-hash owners depend only on the data — computed before
            # reservation so the micro-lock never touches the batch
            owner = _key_owners(cols[self.shard_key], nsh)
            present = np.bincount(owner, minlength=nsh) > 0
        with trace.span("stream/reserve", stream=self.name), \
                self._reserve_lock:
            t = self.reserved
            self.reserved += n
            if owner is None:
                touched = self._touched_shards(t, n)
            else:
                touched = [i for i in range(nsh) if present[i]]
            tickets = {i: self._committers[i].issue() for i in touched}
            self.blocks_reserved += 1
            self.rows_reserved += n
        with self._frontier:
            self._pending_blocks[t] = (n, dict(tickets))
        try:
            with trace.span("stream/stage", stream=self.name, block=t):
                parts = self._partition(cols, n, t, owner)
        except BaseException as exc:
            # never wedge the lanes: release every issued ticket as an
            # empty publish and complete the block — its seqs become a
            # permanent hole (windows over them raise "evicted"), but
            # every other producer keeps flowing
            for i in sorted(touched):
                self._committers[i].commit(tickets[i], lambda: None)
            self._complete_block(t, n, exc)
            raise
        dropped, failure = self._commit_parts(touched, tickets, parts,
                                              n, t)
        # advance the committed frontier over every block whose
        # predecessors have all published (reads only ever see seqs
        # below it, so no read observes this batch while an earlier one
        # is still in flight)
        self._complete_block(t, n, failure)
        self._note_append(n)
        if failure is not None:
            raise failure
        return {"appended": n, "dropped": dropped, "rows": self.num_rows}

    def _complete_block(self, t: int, n: int,
                        failure: Optional[BaseException] = None) -> None:
        """Record block [t, t+n) as done and advance the committed
        frontier over every contiguous finished block — then reap any
        dead block now parked at the frontier, so one killed producer
        can't make every later block invisible forever.

        A block that failed to publish on some ring is a permanent hole;
        a durable stream logs its bounds (``log_hole``) so recovery and
        catch-up step over it as the live frontier did.  A kill (a
        ``BaseException`` that is not an ``Exception``, such as a
        simulated crash) logs nothing: the process is gone."""
        with self._frontier:
            if t >= self.total_appended:      # not reaped meanwhile
                self._finished[t] = n
                if (isinstance(failure, Exception)
                        and self._durable is not None):
                    self._durable.log_hole(t, n, abandoned=False)
            self._advance_frontier_locked()
            self._reap_stalled_locked()
            self._frontier.notify_all()

    def _advance_frontier_locked(self) -> None:
        while self.total_appended in self._finished:
            t = self.total_appended
            self.total_appended += self._finished.pop(t)
            self._pending_blocks.pop(t, None)

    def _reap_stalled_locked(self) -> None:
        """Abandon frontier-blocking blocks that can never complete:
        every commit ticket consumed, at least one by *stealing* (the
        producer died before publishing).  Their seqs become a
        permanent hole — exactly the staging-failure semantics — and
        every later finished block becomes visible."""
        while True:
            entry = self._pending_blocks.get(self.total_appended)
            if entry is None or self.total_appended in self._finished:
                break
            n, tickets = entry
            if not all(self._committers[i].consumed(tk)
                       for i, tk in tickets.items()):
                break
            if not any(self._committers[i].was_stolen(tk)
                       for i, tk in tickets.items()):
                break
            self._pending_blocks.pop(self.total_appended)
            if self._durable is not None:
                self._durable.log_hole(self.total_appended, n,
                                       abandoned=True)
            self.total_appended += n
            self.blocks_abandoned += 1
            self._advance_frontier_locked()

    def _touched_shards(self, t: int, n: int) -> List[int]:
        """Round-robin rings receiving rows of seq block [t, t+n) —
        pure O(rings) arithmetic on the block boundaries, cheap enough
        to run inside the reservation micro-lock."""
        nsh = len(self._rings)
        blk = self.block_rows
        first, last = t // blk, (t + n - 1) // blk
        if last - first + 1 >= nsh:
            return list(range(nsh))
        return sorted({b % nsh for b in range(first, last + 1)})

    def _partition(self, cols: Dict[str, np.ndarray], n: int, t: int,
                   owner: Optional[np.ndarray]
                   ) -> List[Dict[str, np.ndarray]]:
        """Per-ring payloads (each with the reserved seq column) for
        rows [t, t+n).  One ring takes the whole batch; round-robin
        batches spanning few blocks split into contiguous zero-copy
        views; many-block and key-hash batches go through the vectorized
        owner map.  Pure function of its inputs — runs on the producer's
        thread with no locks held."""
        nsh = len(self._rings)
        seqs = np.arange(t, t + n, dtype=np.float64)
        if nsh == 1:
            return [{**cols, SEQ_FIELD: seqs}]
        if owner is None and n // self.block_rows <= 32:
            # round-robin over seq blocks: the ring of seq q is
            # (q // block_rows) % N.  A batch spanning few blocks
            # splits into contiguous zero-copy views at block
            # boundaries (the big-batch ingest fast path)
            blk = self.block_rows
            segs: List[List[Tuple[int, int]]] = [[] for _ in range(nsh)]
            off = 0
            while off < n:
                q = t + off
                take = min(n - off, blk - q % blk)
                segs[(q // blk) % nsh].append((off, off + take))
                off += take
            parts = []
            for i in range(nsh):
                if len(segs[i]) == 1:
                    a, b = segs[i][0]
                    payload = {f: v[a:b] for f, v in cols.items()}
                    payload[SEQ_FIELD] = seqs[a:b]
                else:
                    payload = {f: np.concatenate(
                        [v[a:b] for a, b in segs[i]])
                        for f, v in cols.items()} if segs[i] else \
                        {f: v[:0] for f, v in cols.items()}
                    payload[SEQ_FIELD] = np.concatenate(
                        [seqs[a:b] for a, b in segs[i]]) \
                        if segs[i] else seqs[:0]
                parts.append(payload)
            return parts
        if owner is None:
            # many small blocks: a Python per-segment loop would
            # dominate — compute owners vectorized instead
            owner = ((t + np.arange(n)) // self.block_rows) % nsh
        parts = []
        for i in range(nsh):
            idx = np.nonzero(owner == i)[0]
            payload = {f: v[idx] for f, v in cols.items()}
            payload[SEQ_FIELD] = seqs[idx]
            parts.append(payload)
        return parts

    def _commit_parts(self, touched: List[int], tickets: Dict[int, int],
                      parts: List[Dict[str, np.ndarray]], n: int,
                      t: int) -> Tuple[int, Optional[BaseException]]:
        """Publish each staged payload through its ring's ordered
        committer; returns (rows overwritten, first failure).  Every
        issued ticket MUST commit — even on failure — or later blocks
        on that ring would wait forever: a publish that raises is
        recorded and its lane still advances (`_OrderedCommitter.commit`
        runs its release in a finally).  The ring resolves inside the
        closure, so a block reserved before a live ring move publishes
        to wherever the ring lives when its turn comes.

        A single large batch fans its commits out to the pool when no
        other producer holds it; contenders commit inline in ring
        order.  Inline commits cannot deadlock: tickets follow global
        reservation order, so an earlier producer never waits on a
        later one — and the pool is gated to one producer because its
        queue could otherwise park an earlier producer's ring write
        behind a later producer's waiting task."""
        failures: List[BaseException] = []

        def publish(i: int) -> int:
            payload = parts[i]

            def ring_write() -> int:
                dropped = self._rings[i].write(payload)
                if self._durable is not None:
                    # write-behind per-ring log: inside this lane's
                    # ordered section (records stay in seq order per
                    # lane) but outside the ring lock (readers never
                    # wait on log I/O); the record carries the block
                    # bounds so recovery can cut an incompletely-logged
                    # block
                    self._durable.log_shard(i, t, n, payload)
                return dropped

            try:
                with trace.span("committer/commit", stream=self.name,
                                shard=i, ticket=tickets[i]):
                    return self._committers[i].commit(tickets[i],
                                                      ring_write)
            except BaseException as exc:     # noqa: BLE001 — re-raised
                failures.append(exc)
                return 0

        order = sorted(touched)
        if (len(order) > 1 and n >= self.PARALLEL_APPEND_MIN_ROWS
                and self._pool_gate.acquire(blocking=False)):
            try:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=len(self._rings),
                        thread_name_prefix=f"scatter-{self.name}")
                dropped = sum(self._pool.map(trace.bind(publish), order))
            finally:
                self._pool_gate.release()
        else:
            dropped = sum(publish(i) for i in order)
        return dropped, failures[0] if failures else None

    def _apply_block(self, t: int, n: int,
                     parts: Dict[int, Dict[str, np.ndarray]],
                     abandoned: bool = False) -> None:
        """Re-apply logged block [t, t+n) — ``parts`` maps a ring to its
        slice; for a hole, only the slices that did publish — exactly
        as its live commit did: the same ring writes, then the frontier
        and reservation counters (and ``blocks_abandoned`` for a block
        the frontier reaped).  Recovery and replica catch-up only, never
        concurrent with ingest."""
        assert t == self.total_appended, (t, self.total_appended)
        for i in sorted(parts):
            self._rings[i].write(parts[i])
        with self._frontier:
            self.total_appended += n
        self.reserved = self.total_appended
        self.blocks_reserved += 1
        self.rows_reserved += n
        self.blocks_abandoned += abandoned

    # -- event-time ingest ----------------------------------------------------
    def _classify_late_locked(self, cols: Dict[str, np.ndarray], n: int
                              ) -> Tuple[Dict[str, np.ndarray], int, int]:
        """Split an arriving batch against the low watermark: rows
        strictly below it can no longer be inserted in timestamp order,
        so they are dropped and counted on ``total_late``.  Returns
        (kept columns, kept count, late count).  ``ts == watermark`` is
        NOT late: every flushed row has ts <= watermark, so an equal row
        still appends in order.

        With a dead-letter sink attached (``register_stream(...,
        dead_letter=True)``), late rows also land in the ``{name}.__late``
        side stream — queryable history instead of only a counter.  The
        sink is a plain seq stream with its own locks, so appending to
        it under this stream's lock cannot deadlock, and the sink append
        is in arrival order (this lock serializes arrivals), so replay
        reproduces it deterministically."""
        late_mask = cols[self.ts_field] < self.watermark
        nlate = int(late_mask.sum())
        if nlate:
            self.total_late += nlate
            if self._late_sink is not None:
                self._late_sink._append_seq(
                    {f: v[late_mask] for f, v in cols.items()}, nlate)
            keep = ~late_mask
            cols = {f: v[keep] for f, v in cols.items()}
        return cols, n - nlate, nlate

    def _append_event_time(self, cols: Dict[str, np.ndarray],
                           n: int) -> Dict[str, int]:
        """Bounded out-of-order ingest: rows at or above the low watermark
        park in the insertion buffer; the watermark then advances and
        everything it passed is flushed into the rings in timestamp
        order.  Rows below the watermark are late — counted and
        dropped, never inserted out of order.  Key-hashed streams track
        a per-ring max timestamp and take the *minimum* across rings
        with data as the watermark basis, so one lagging ring holds
        every window open (``flush()`` and ``idle_timeout`` are the
        punctuation for idle rings)."""
        with trace.span("stream/stage", stream=self.name,
                        rows=n), self._lock:
            self._last_arrival = self._now()
            if self._durable is not None:
                # log the arrival batch BEFORE late classification: the
                # log carries every row that arrived (late ones
                # included), so replay re-runs classification and
                # reproduces total_late and the dead-letter sink
                self._durable.log_arrive(cols, n)
            cols, kept, nlate = self._classify_late_locked(cols, n)
            if kept:
                ts = cols[self.ts_field]
                self._pending.append(cols)
                self._pending_rows += kept
                self.max_ts_seen = max(self.max_ts_seen, float(ts.max()))
                self.min_ts_seen = min(self.min_ts_seen, float(ts.min()))
                if self.shard_key is not None:
                    owner = _key_owners(cols[self.shard_key],
                                        len(self._rings))
                    for i in range(len(self._rings)):
                        sel = owner == i
                        if sel.any():
                            self._shard_max_ts[i] = max(
                                self._shard_max_ts[i],
                                float(ts[sel].max()))
                            self._shard_last_arrival[i] = \
                                self._last_arrival
            flushed, dropped = self._flush_locked(
                self._watermark_candidate_locked())
            self._note_append(kept)
            return {"appended": kept, "dropped": dropped, "late": nlate,
                    "flushed": flushed, "pending": self._pending_rows,
                    "rows": self.num_rows}

    def _watermark_candidate_locked(self) -> float:
        """The low-watermark basis: the global max timestamp, or for a
        key-hashed stream the ``min`` across rings that hold data (a
        ring that has never seen a row cannot declare other rows late
        and is excluded until it does; round-robin rings receive
        interleaved blocks, so their minima coincide with the max).

        With ``idle_timeout`` set, a key-hashed ring whose key range
        has received nothing for that many seconds is also excluded —
        one quiet ring no longer stalls the stream minimum.  When
        *every* data-bearing ring has gone idle the basis falls back to
        the global max timestamp, flushing the stream out entirely."""
        if self.shard_key is None:
            return self.max_ts_seen - self.max_delay
        now = self._now() if self.idle_timeout is not None else None
        seen, idle_excluded = [], False
        for i, t in enumerate(self._shard_max_ts):
            if t == float("-inf"):
                continue
            last = self._shard_last_arrival[i]
            if (now is not None and last is not None
                    and now - last >= self.idle_timeout):
                idle_excluded = True
                continue
            seen.append(t)
        if not seen:
            if idle_excluded:
                return self.max_ts_seen - self.max_delay
            return float("-inf")
        return min(seen) - self.max_delay

    def _flush_locked(self, new_watermark: float) -> Tuple[int, int]:
        """Advance the (monotone) watermark and flush every buffered row
        it passed, sorted by timestamp (stable, so equal-ts rows keep
        arrival order — the buffer is always in arrival order), with
        the global seq block reserved HERE in that order, then
        partitioned to the rings.  Returns (rows flushed, rows dropped
        by the rings).

        Event-time ingest is serialized on the stream lock, so the rings
        are written directly (no commit lanes).  The frontier and the
        watermark move only after the rows are in: a reader without the
        stream lock (a compiled plan) that sees a window closed finds
        every row of it in the rings."""
        wm = max(self.watermark, new_watermark)
        m = dropped = 0
        if self._pending and wm != float("-inf"):
            cat = {f: np.concatenate([b[f] for b in self._pending])
                   for f in self.fields}
            ts = cat[self.ts_field]
            ready = ts <= wm
            m = int(ready.sum())
        if m:
            order = np.argsort(ts[ready], kind="stable")
            flush_cols = {f: v[ready][order] for f, v in cat.items()}
            if m < ts.shape[0]:
                hold = ~ready
                self._pending = [{f: v[hold] for f, v in cat.items()}]
            else:
                self._pending = []
            self._pending_rows -= m
            t = self.total_appended
            owner = (_key_owners(flush_cols[self.shard_key],
                                 len(self._rings))
                     if self.shard_key is not None else None)
            dropped = sum(ring.write(payload) for ring, payload in
                          zip(self._rings,
                              self._partition(flush_cols, m, t, owner))
                          if payload[SEQ_FIELD].shape[0])
            self.total_appended = self.reserved = t + m
            self.blocks_reserved += 1
            self.rows_reserved += m
        self.watermark = wm
        return m, dropped

    def flush(self, to_ts: Optional[float] = None) -> Dict[str, Any]:
        """Punctuation: force the watermark up to ``to_ts`` (default: the
        max timestamp seen, flushing the whole insertion buffer).  The
        escape hatch for idle feeds and idle key ranges — without new
        rows the watermark never advances on its own."""
        with self._lock:
            if self.ts_field is None:
                raise StreamException(
                    f"stream {self.name!r} has no event-time field")
            target = self.max_ts_seen if to_ts is None else float(to_ts)
            if self._durable is not None and target > self.watermark:
                # punctuation is external input (wall clock / operator),
                # not derivable from arrivals — log the resolved target
                # so replay applies the same watermark advance
                self._durable.log_flush(target)
            flushed, dropped = self._flush_locked(target)
            return {"flushed": flushed, "dropped": dropped,
                    "watermark": self.watermark,
                    "pending": self._pending_rows}

    def advance_idle_watermark(self) -> Dict[str, Any]:
        """Automatic punctuation: re-evaluate the watermark basis with
        idle key-hashed rings excluded, and when the whole stream has
        seen no arrivals for ``idle_timeout`` seconds advance it to the
        max timestamp seen (== ``flush()``), so a quiet feed's buffered
        rows and open windows don't stall forever.  A no-op while
        nothing changes, without ``idle_timeout``, or without an
        event-time axis.  ``StreamRuntime.tick`` calls this every tick
        for every such stream."""
        if self.ts_field is None or self.idle_timeout is None:
            return {"flushed": 0, "dropped": 0}
        with self._lock:
            target = self._watermark_candidate_locked()
            if (self._last_arrival is not None
                    and self._now() - self._last_arrival
                    >= self.idle_timeout):
                target = max(target, self.max_ts_seen)
            if target <= self.watermark:
                # every buffered row is above the watermark already
                return {"flushed": 0, "dropped": 0}
            if self._durable is not None:
                # idle punctuation is wall-clock input: log the resolved
                # target so replay advances the same watermark without
                # re-evaluating idleness
                self._durable.log_flush(target)
            flushed, dropped = self._flush_locked(target)
            return {"flushed": flushed, "dropped": dropped}

    # -- views ----------------------------------------------------------------
    def _gather(self, field: str, lo: float, hi: float
                ) -> Dict[str, np.ndarray]:
        """The seq column and every field of the rows whose ``field``
        lies in [lo, hi), in global seq order.  Each ring contributes
        one sorted run located by binary search — ``field`` is the seq
        column, or an event-time stream's ts (seqs are assigned in ts
        order) — read in one point-in-time cut, so the cost scales with
        the range rather than the buffered rows.  Caller holds the
        lock."""
        names = (SEQ_FIELD,) + self.fields
        with trace.span("stream/gather", stream=self.name,
                        path="interpreted") as sp:
            with _Ring.cut(self._rings):
                runs = [ring.rows_locked(field, lo, hi, names)
                        for ring in self._rings]
            rows = _merge_runs(runs)
            sp.set(rows=int(rows[SEQ_FIELD].shape[0]))
        return rows

    def snapshot(self) -> dm.Table:
        """Every row below the committed frontier, in seq order."""
        with self._lock:
            rows = self._gather(SEQ_FIELD, -math.inf, self.total_appended)
        out = {"seq": to_device(rows[SEQ_FIELD].astype(np.int64))}
        for f in self.fields:
            out[f] = to_device(rows[f])
        return dm.Table(out)

    def window(self, size: int,
               slide: Optional[int] = None) -> dm.ArrayObject:
        """Tumbling (``slide`` is None) or sliding window over the
        logical seq space."""
        assert size > 0
        with self._lock:
            total = self.total_appended
            if slide is None:
                s = _tumbling_start(self.name, size, total)
                rows = self._gather(SEQ_FIELD, s, s + size)
                got = rows[SEQ_FIELD].shape[0]
                if got != size:
                    raise _window_evicted(self.name, s, s + size, got)
                return dm.ArrayObject(
                    {f: to_device(rows[f]) for f in self.fields},
                    ("tick",))
            assert slide > 0
            # read against the same frontier snapshot ``total`` — a
            # block committing mid-call must not skew the suffix math
            rows = self._gather(SEQ_FIELD, -math.inf, total)
        seqs = rows[SEQ_FIELD]
        # the contiguous suffix of the seq space still fully buffered
        gaps = np.nonzero(seqs != np.arange(total - seqs.shape[0],
                                            total))[0]
        a = int(gaps[-1]) + 1 if gaps.size else 0
        count = seqs.shape[0] - a
        if count < size:
            raise _too_few_rows(self.name, count, size)
        starts = np.arange(a, a + count - size + 1, slide)
        return dm.ArrayObject(
            {f: to_device(np.stack([rows[f][s:s + size] for s in starts]))
             for f in self.fields}, ("window", "tick"))

    def ewindow(self, span: float,
                slide: Optional[float] = None) -> dm.ArrayObject:
        """Latest *closed* event-time window as a 1-D ArrayObject.

        Windows are aligned to multiples of ``slide`` (default: ``span``,
        i.e. tumbling) on the timestamp axis; a window ``[s, s + span)``
        is closed only once the low watermark reaches its end, so its
        contents can no longer change (any row that could still land in
        it would be late).  Unlike seq windows the row count varies with
        event density — an empty closed window is legitimate.  Raises
        until the first window closes, and when a ring has already
        evicted rows the window covered (no silent partials)."""
        start, end = _latest_closed_ewindow(self, span, slide)
        with self._lock:
            evicted = self.evicted_ts
            if start <= evicted:
                raise _ewindow_evicted(self.name, start, end, evicted)
            rows = self._gather(self.ts_field, start, end)
        return dm.ArrayObject({f: to_device(rows[f]) for f in self.fields},
                              ("tick",))

    def window_aggregate(self, size: int, fn: str, field: str) -> float:
        """Aggregate over the latest complete tumbling window without
        materializing it: each ring's partial over the window's seq
        range (count/sum/avg O(1) through its cumulative ring, min/max
        over its slice), combined.  Repeated calls for the same window
        index return the memoized value (the standing-query fast path:
        ticks faster than window completion cost O(1), and the value
        survives ring eviction)."""
        assert fn in _COMBINABLE_AGGS, fn
        with self._lock:
            s = _tumbling_start(self.name, size, self.total_appended)
            key = (fn, field, size)
            cached = self._agg_cache.get(key)
            if cached is not None and cached[0] == s // size:
                self.agg_cache_hits += 1
                return cached[1]
            with _Ring.cut(self._rings):
                partials = [r.partial_locked(fn, field, s, s + size)
                            for r in self._rings]
            rows = sum(c for _, c in partials)
            if rows != size:
                raise _window_evicted(self.name, s, s + size, rows)
            values = [v for v, c in partials if c]
            if fn == "count":
                value = float(size)
            elif fn == "min":
                value = min(values)
            elif fn == "max":
                value = max(values)
            else:
                value = functools.reduce(operator.add, values)
                if fn == "avg":
                    value /= size
            self.agg_computes += 1
            self._agg_cache[key] = (s // size, value)
            return value

    # -- rate & stats ---------------------------------------------------------
    def rate(self) -> float:
        """Recent ingest rate in rows/second (0.0 with <2 appends)."""
        with self._rate_lock:
            return _recent_rate(self._append_times)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {
                "rows": self.num_rows, "capacity": self.capacity,
                "appended": self.total_appended,
                "dropped": self.total_dropped,
                "shard_key": self.shard_key,
                "agg_cache_hits": self.agg_cache_hits,
                "agg_computes": self.agg_computes,
                "ingest_concurrency": self.ingest_concurrency(),
                "shards": self.shard_stats()}
            if self.ts_field is not None:
                # None until the watermark starts, keeping status()
                # JSON-serializable
                wm = self.watermark
                out.update(ts_field=self.ts_field,
                           max_delay=self.max_delay,
                           idle_timeout=self.idle_timeout,
                           watermark=None if wm == float("-inf") else wm,
                           late=self.total_late,
                           pending=self._pending_rows)
                if self.shard_key is not None:
                    # per-ring watermark views: the stream watermark is
                    # their minimum (over rings that have data)
                    out["shard_watermarks"] = {
                        i: (None if t == float("-inf")
                            else t - self.max_delay)
                        for i, t in enumerate(self._shard_max_ts)}
            return out

    def shard_stats(self) -> Dict[int, Dict[str, Any]]:
        """Per-ring ingest/drop health (the Monitor's rebalance signal).
        A ring's ``rows_per_second`` is the stream's rate times the
        ring's share of the rows written."""
        rate = self.rate()
        with self._lock:
            out = {i: dict(ring.stats(), engine=ename) for i, (ename, ring)
                   in enumerate(zip(self._engines, self._rings))}
        written = sum(st["appended"] for st in out.values())
        for st in out.values():
            st["rows_per_second"] = (round(rate * st["appended"] / written, 1)
                                     if written else 0.0)
        return out

    # -- live ring migration --------------------------------------------------
    def migrate_shard(self, idx: int, migrator, engines: Dict[str, Any],
                      to_engine: str):
        """Move ring ``idx`` to another StreamEngine through the
        Migrator's ``stream`` route.  The stream lock keeps standing
        queries from observing a half-moved ring, and the ring's ordered
        committer is **paused** across the move: every seq block
        reserved before the pause drains into the old ring first (it
        travels with the exported state), blocks reserved during the
        move wait and then publish into the new ring — in-flight
        reservations are carried, never lost.  Seq watermark and drop
        counters travel with the state (the Migrator keeps the
        catalog's placement truthful)."""
        from repro.core.migrator import MigrationParams
        with trace.span("migrator/shard_move", stream=self.name,
                        shard=idx, dst=to_engine), self._lock:
            if not (0 <= idx < len(self._rings)
                    and self._engines[idx] is not None):
                raise ValueError(
                    f"{self.name!r} has no shard {idx} placed on an "
                    f"engine ({len(self._rings)} rings)")
            if to_engine not in engines:
                raise ValueError(
                    f"migration target engine {to_engine!r} does not "
                    f"exist (shard {idx} of {self.name!r} stays on "
                    f"{self._engines[idx]})")
            src_name = self._engines[idx]
            if to_engine == src_name:
                raise ValueError(
                    f"shard {idx} of {self.name!r} already on {to_engine}")
            obj_name = self.shard_name(idx)
            committer = self._committers[idx]
            committer.pause()        # drain in-flight blocks, hold later
            try:
                result = migrator.migrate(
                    engines[src_name], obj_name, engines[to_engine],
                    obj_name, MigrationParams(method="stream"))
                self._rings[idx] = engines[to_engine].get(obj_name)
                self._engines[idx] = to_engine
            finally:
                committer.resume()   # held blocks publish to the new ring
            self.migrations += 1
            # the destination now participates: it must resolve the
            # logical name too (ring-transparent reads, planner pin)
            if not engines[to_engine].has(self.name):
                engines[to_engine].put(self.name, self)
            return result

    # -- state: export / clone / checkpoint -----------------------------------
    def _export_locked(self) -> Dict[str, Any]:
        """The full state, every ring included (caller holds the lock
        and has kept every writer off the rings — see
        ``_checkpoint_snapshot``)."""
        return {
            "name": self.name, "fields": self.fields,
            "rolling": self.rolling, "shard_key": self.shard_key,
            "block_rows": self.block_rows, "ts_field": self.ts_field,
            "max_delay": self.max_delay,
            "idle_timeout": self.idle_timeout,
            "engines": list(self._engines),
            "rings": [ring.export_state() for ring in self._rings],
            "total_appended": self.total_appended,
            "blocks_reserved": self.blocks_reserved,
            "rows_reserved": self.rows_reserved,
            "blocks_abandoned": self.blocks_abandoned,
            "watermark": self.watermark,
            "max_ts_seen": self.max_ts_seen,
            "min_ts_seen": self.min_ts_seen,
            "total_late": self.total_late,
            # the insertion buffer must travel with a live move or
            # pending rows are lost
            "pending": [{f: v.copy() for f, v in b.items()}
                        for b in self._pending],
            "shard_max_ts": list(self._shard_max_ts),
            "migrations": self.migrations,
            "append_times": list(self._append_times),
        }

    def export_state(self) -> Dict[str, Any]:
        """Deep-copy the full live state — every ring's rows, cumulative
        slots and counters, the seq frontier, the event-time buffer and
        watermark, rate history — so a Migrator can rebuild the stream
        on another StreamEngine without losing standing-query
        continuity.  Reservations are frozen and every lane drained
        first, so the exported frontier equals the reservation counter
        (in-flight blocks are carried, not lost).  Blocks reserved
        *after* the export still land in this object — a direct move
        must pause its producers (documented on the Migrator's stream
        route); ring moves are safe because ``migrate_shard`` holds the
        ring's lane paused across the whole move."""
        state, _ = self._checkpoint_snapshot(lambda: None)
        return state

    def _checkpoint_snapshot(self, capture):
        """Export the full state at an instant where every ring, the
        committed frontier and the write-behind segment log agree,
        running ``capture()`` (the durability layer reads its per-lane
        log positions) at that same instant.  Returns (state dict,
        capture()'s result).

        Event-time streams do all ring writes and logging under the
        stream lock, so that lock is the coherence point.  Seq-ordered
        ones freeze reservations (micro-lock), drain every lane (logs
        are written inside the lanes' ordered sections), then wait for
        the committed frontier to reach the reservation counter —
        block completion runs on producer threads right after their
        last lane commit, so this wait is bounded."""
        if self.ts_field is not None:
            with self._lock:
                return self._export_locked(), capture()
        with self._reserve_lock:
            for committer in self._committers:
                committer.pause()
            try:
                deadline = time.monotonic() + 60.0
                with self._frontier:
                    while self.total_appended < self.reserved:
                        if not self._frontier.wait(
                                timeout=deadline - time.monotonic()):
                            raise StreamException(
                                f"stream {self.name!r}: checkpoint "
                                f"frontier settle timed out at "
                                f"{self.total_appended}/{self.reserved}")
                        self._reap_stalled_locked()
                with self._lock:
                    state = self._export_locked()
                return state, capture()
            finally:
                for committer in self._committers:
                    committer.resume()

    @classmethod
    def from_state(cls, state: Dict[str, Any]) -> "Stream":
        rings = [_Ring.from_state(r) for r in state["rings"]]
        stream = cls(state["name"], state["fields"], rings[0].capacity,
                     rolling=state["rolling"],
                     ts_field=state["ts_field"],
                     max_delay=state["max_delay"],
                     idle_timeout=state["idle_timeout"],
                     shards=state["engines"],
                     shard_key=state["shard_key"],
                     block_rows=state["block_rows"])
        stream._rings = rings
        # in-flight reservations at export time were drained into the
        # frontier, so the restored reservation counter IS the frontier
        stream.total_appended = stream.reserved = \
            int(state["total_appended"])
        stream.blocks_reserved = int(state["blocks_reserved"])
        stream.rows_reserved = int(state["rows_reserved"])
        stream.blocks_abandoned = int(state["blocks_abandoned"])
        stream.watermark = float(state["watermark"])
        stream.max_ts_seen = float(state["max_ts_seen"])
        stream.min_ts_seen = float(state["min_ts_seen"])
        stream.total_late = int(state["total_late"])
        stream._pending = [{f: np.asarray(v, np.float64)
                            for f, v in b.items()}
                           for b in state["pending"]]
        stream._pending_rows = sum(
            b[stream.fields[0]].shape[0] for b in stream._pending)
        stream._shard_max_ts = [float(t) for t in state["shard_max_ts"]]
        stream.migrations = int(state["migrations"])
        stream._append_times.extend(state["append_times"])
        return stream

    def clone(self, name: Optional[str] = None,
              state: Optional[Dict[str, Any]] = None) -> "Stream":
        """A detached deep copy of the live state (every ring included),
        optionally renamed — what the Migrator's stream-route *copy*
        mode (read replicas) builds on.  The clone shares nothing with
        this stream: no committer, no durability hook, no late sink;
        its rings are renamed too, so a replica's diagnostics never
        alias the primary's.  Pass ``state`` (an ``export_state`` dict
        captured earlier, e.g. inside ``_checkpoint_snapshot``) to
        clone that instant instead of now."""
        state = dict(self.export_state() if state is None else state)
        if name is not None:
            state["name"] = name
            n = len(state["rings"])
            state["rings"] = [dict(r, name=_ring_name(name, i, n))
                              for i, r in enumerate(state["rings"])]
        return Stream.from_state(state)

    def close(self) -> None:
        """Shut down the scatter fan-out pool.  Optional: a dropped
        stream's pool is reclaimed when the executor is garbage
        collected (its workers exit via the stdlib's weakref hook);
        call this for deterministic teardown in tests/benchmarks."""
        with self._lock:
            if self._pool is not None:
                self._pool.shutdown(wait=True)
                self._pool = None


def movable(obj, copy: bool) -> bool:
    """Whether the Migrator's ``stream`` route takes ``obj``: a ring of
    a multi-ring stream moves (``Stream.migrate_shard``), a one-ring
    stream moves whole, and any stream can be copied (a read replica);
    a ring alone is never copied."""
    if isinstance(obj, Stream):
        return copy or obj.num_shards == 1
    return isinstance(obj, _Ring) and not copy


class StreamEngine(Engine):
    """S-Store analog: holds named ``Stream`` objects (and the
    ``{name}@shardN`` rings of multi-ring streams) for the streaming
    island.  Materialized window views (plain Tables/ArrayObjects) pass
    through the inherited binary/staged import/export paths, so the
    Migrator can cast them into the other islands unchanged."""
    kind = "stream_store"
    islands = ("streaming",)

    def create_stream(self, name: str, fields: Sequence[str],
                      capacity: int = 4096) -> Stream:
        stream = Stream(name, fields, capacity)
        self.put(name, stream)
        return stream

    def streams(self) -> Dict[str, Stream]:
        """The logical streams this engine serves (a multi-ring
        stream's handle lives on every engine holding one of its rings;
        the rings themselves are not streams)."""
        return {n: o for n, o in self._objects.items()
                if isinstance(o, Stream)}


ENGINE_KINDS["stream_store"] = StreamEngine
