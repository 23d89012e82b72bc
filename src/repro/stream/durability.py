"""Crash-safe streams: per-ring append-only segment log + checkpoint /
recover / replay (ROADMAP direction 5).

Durability is **opt-in per stream** (``register_stream(...,
durability=dir)`` or :func:`attach`).  Three pieces:

- **Segment log** (``<dir>/wal/<lane>/seg_*.log``): an append-only
  binary record log written *write-behind* from the PR-5 ordered
  committers — a batch is logged inside its lane's ordered commit
  section, after the ring write published, so the ingest hot path
  gains no locks and readers never wait on log I/O.  Lanes:

  * seq-ordered streams: one lane **per ring** of ``SHARD`` records
    (the ring's slice of a block, seq column included), each carrying
    its block's bounds so recovery can cut a block whose rings were not
    all logged before a crash — a one-ring stream logs one lane — plus
    a ``holes`` lane of ``HOLE`` records: the bounds of every block that
    will never fully publish (a failed stage or ring commit, or a block
    the frontier reaped after its producer stalled), so recovery and
    replica catch-up step over it instead of stopping there;
  * event-time streams: ingest is lock-serialized, so one lane of
    ``ARRIVE`` records (the raw arrival batches, logged before
    late classification so replay reproduces ``total_late`` and the
    dead-letter sink) plus ``FLUSH`` records for explicit/idle
    punctuation (external input a replay cannot re-derive).

  Records are CRC-checked and length-framed; a torn tail (real kill or
  an armed ``runtime.fault`` crash point) is detected and truncated on
  recovery.

- **Checkpoints** through the seed's ``checkpoint/manager.py`` (atomic
  manifest promote, keep-last-k): the stream's full ``export_state``
  plus the per-lane log positions, captured at one coherent instant
  (reservations frozen, lanes drained — see
  ``Stream._checkpoint_snapshot``).  After a checkpoint, log segments
  no retained checkpoint needs are pruned.

- **recover()**: restore the latest checkpoint (or a fresh stream),
  replay the log tail through the *same* ingest code paths, and hand
  back a stream whose ``total_appended``, seq assignment, watermarks,
  eviction counters, pending buffers, and rolling aggregates are
  bit-identical to the pre-crash stream's durable prefix — the house
  invariant gains ``recovered ≡ original``.  Replay doubles as a
  deterministic load generator (``replay(S)`` in BQL; the
  ``stream/replay_rate`` bench row measures replayed rows/sec against
  live ingest).

Determinism caveat (documented in docs/OPERATIONS.md): with
``idle_timeout`` set, idle-watermark punctuation is wall-clock input —
it is durable *as logged* (tick-driven advances write ``FLUSH``
records), but an idle exclusion coinciding with an arrival is not
re-derived by replay.
"""
from __future__ import annotations

import dataclasses
import json
import os
import struct
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.obs import metrics, trace
from repro.runtime.fault import crash_point
from repro.stream.engine import SEQ_FIELD, Stream, StreamException

# -- record framing ----------------------------------------------------------
# little-endian: lsn u64 | kind u8 | block i64 | block_total i64 |
#                nrows u32 | payload_len u32 | crc32(payload) u32
_HDR = struct.Struct("<QBqqIII")

KIND_SHARD = 2       # one ring's slice of a block  payload: fields+__seq
KIND_ARRIVE = 3      # raw event-time arrival batch payload: fields
KIND_FLUSH = 4       # punctuation                  payload: target ts
KIND_HOLE = 5        # a block that never published payload: 1.0 if reaped
# records whose payload is one float64 (``Record.target``), not columns
_SCALAR_KINDS = (KIND_FLUSH, KIND_HOLE)

_META_KEY = "meta"   # checkpoint leaf holding the JSON-encoded structure


@dataclasses.dataclass
class Record:
    lsn: int
    kind: int
    block: int
    total: int
    nrows: int
    cols: Optional[Dict[str, np.ndarray]]   # None for FLUSH and HOLE
    target: float                           # FLUSH and HOLE only
    size: int                               # bytes on disk


class SegmentLog:
    """One lane's append-only record log, split into size-rolled
    segment files ``seg_<first_lsn>.log``.  Writers are externally
    serialized (the lane's ordered committer / the stream lock), so
    ``append`` takes no lock of its own."""

    def __init__(self, directory: str, fields: Tuple[str, ...],
                 segment_bytes: int = 1 << 20,
                 fsync: bool = False) -> None:
        self.directory = directory
        self.fields = tuple(fields)
        self.segment_bytes = int(segment_bytes)
        self.fsync = fsync
        os.makedirs(directory, exist_ok=True)
        self._file = None
        self._file_size = 0
        self.next_lsn = 0
        self.records = 0          # records written by THIS handle
        self.rows = 0             # data rows written by this handle
        self.bytes = 0
        self._open_at_end()

    # -- file plumbing ---------------------------------------------------------
    def _segments(self) -> List[Tuple[int, str]]:
        out = []
        if not os.path.isdir(self.directory):
            # the WAL was pruned/removed out from under a closed handle;
            # readers (Monitor stats) must see "empty", not crash a tick
            return out
        for name in os.listdir(self.directory):
            if name.startswith("seg_") and name.endswith(".log"):
                out.append((int(name[4:-4]),
                            os.path.join(self.directory, name)))
        return sorted(out)

    def _open_at_end(self) -> None:
        """Position for appending: scan existing segments (repairing a
        torn tail) to find the next lsn, then open the last segment."""
        segs = self._segments()
        if not segs:
            return
        for first, path in segs:
            recs, clean_end, torn = _scan_segment(path, first,
                                                  self.fields)
            if torn:
                os.truncate(path, clean_end)
            self.next_lsn = first + len(recs)
            if torn:
                break
        last_path = [p for f, p in segs if f <= self.next_lsn][-1]
        self._file = open(last_path, "ab")
        self._file_size = os.path.getsize(last_path)

    def _writer(self, incoming: int):
        if self._file is None or (self._file_size > 0
                                  and self._file_size + incoming
                                  > self.segment_bytes):
            if self._file is not None:
                self._file.close()
            path = os.path.join(self.directory,
                                f"seg_{self.next_lsn:012d}.log")
            self._file = open(path, "ab")
            self._file_size = os.path.getsize(path)
        return self._file

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None

    # -- write -----------------------------------------------------------------
    def append(self, kind: int, block: int, total: int,
               cols: Optional[Dict[str, np.ndarray]], nrows: int,
               target: float = 0.0) -> int:
        """Serialize one record.  Crash points bracket the two writes so
        an armed kill produces exactly the on-disk states a real kill
        could: nothing, a torn (header-only) record, or a whole record
        with the in-memory successor state lost."""
        if kind in _SCALAR_KINDS:
            payload = np.float64(target).tobytes()
        else:
            payload = b"".join(
                np.ascontiguousarray(cols[f], np.float64).tobytes()
                for f in self.fields)
        lsn = self.next_lsn
        hdr = _HDR.pack(lsn, kind, block, total, nrows, len(payload),
                        zlib.crc32(payload))
        crash_point("stream/log:before")
        f = self._writer(len(hdr) + len(payload))
        f.write(hdr)
        crash_point("stream/log:torn", flush=f.flush)
        f.write(payload)
        f.flush()
        if self.fsync:
            os.fsync(f.fileno())
        crash_point("stream/log:after", flush=None)
        self.next_lsn = lsn + 1
        self._file_size += len(hdr) + len(payload)
        self.records += 1
        self.rows += nrows
        self.bytes += len(hdr) + len(payload)
        return lsn

    # -- read ------------------------------------------------------------------
    def scan(self, start_lsn: int = 0,
             repair: bool = False) -> List[Record]:
        """Records with ``lsn >= start_lsn`` in order, stopping at (and
        with ``repair=True`` physically truncating) the first torn or
        corrupt record.  ``repair=False`` is the live-replay mode: a
        concurrent writer's half-flushed tail is skipped, not cut."""
        out: List[Record] = []
        for first, path in self._segments():
            recs, clean_end, torn = _scan_segment(path, first,
                                                  self.fields)
            out.extend(r for r in recs if r.lsn >= start_lsn)
            if torn:
                if repair:
                    os.truncate(path, clean_end)
                break
        return out

    def truncate_from(self, lsn: int) -> int:
        """Physically discard record ``lsn`` and everything after it
        (recovery's cut for blocks that did not fully log before a
        crash).  Returns the number of records discarded."""
        self.close()
        discarded = 0
        for first, path in self._segments():
            if first >= lsn:
                recs, _, _ = _scan_segment(path, first, self.fields)
                discarded += len(recs)
                os.remove(path)
                continue
            recs, _, _ = _scan_segment(path, first, self.fields)
            keep = [r for r in recs if r.lsn < lsn]
            if len(keep) < len(recs):
                discarded += len(recs) - len(keep)
                os.truncate(path, sum(r.size for r in keep))
        self.next_lsn = min(self.next_lsn, lsn)
        self._open_at_end()
        return discarded

    def prune_below(self, lsn: int) -> int:
        """Delete whole segments every record of which is below ``lsn``
        (already covered by every retained checkpoint).  Returns the
        number of segments removed."""
        segs = self._segments()
        removed = 0
        for i, (first, path) in enumerate(segs):
            nxt = segs[i + 1][0] if i + 1 < len(segs) else None
            if nxt is not None and nxt <= lsn:
                os.remove(path)
                removed += 1
        return removed


def _scan_segment(path: str, first_lsn: int, fields: Tuple[str, ...]
                  ) -> Tuple[List[Record], int, bool]:
    """(records, clean end offset, torn?) for one segment file.  Any
    short header, short payload, CRC mismatch, or lsn discontinuity
    marks the tail torn from that offset on."""
    with open(path, "rb") as f:
        data = f.read()
    out: List[Record] = []
    off, expected = 0, first_lsn
    while off + _HDR.size <= len(data):
        lsn, kind, block, total, nrows, paylen, crc = \
            _HDR.unpack_from(data, off)
        end = off + _HDR.size + paylen
        if lsn != expected or end > len(data):
            return out, off, True
        payload = data[off + _HDR.size:end]
        if zlib.crc32(payload) != crc:
            return out, off, True
        if kind in _SCALAR_KINDS:
            cols, target = None, float(np.frombuffer(payload,
                                                     np.float64)[0])
        else:
            flat = np.frombuffer(payload, np.float64)
            if flat.shape[0] != nrows * len(fields):
                return out, off, True
            cols = {f: flat[i * nrows:(i + 1) * nrows].copy()
                    for i, f in enumerate(fields)}
            target = 0.0
        out.append(Record(lsn, kind, block, total, nrows, cols, target,
                          end - off))
        off, expected = end, expected + 1
    return out, off, off < len(data)


# -- checkpoint state <-> flat-array encoding --------------------------------
#
# export_state dicts mix ndarrays with scalars/lists/tuples.  Arrays
# become individual checkpoint leaves (CheckpointManager saves each as
# .npy); everything else lands in one JSON spec with $-tagged wrappers,
# stored as a 0-d unicode array leaf — self-describing, so recovery
# needs no template pytree.

def _encode(obj, path: str, arrays: Dict[str, np.ndarray]):
    if isinstance(obj, np.ndarray):
        arrays[path] = obj
        return {"$a": path}
    if isinstance(obj, dict):
        return {"$d": {k: _encode(v, f"{path}/{k}", arrays)
                       for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        enc = [_encode(v, f"{path}/{i}", arrays)
               for i, v in enumerate(obj)]
        return {"$t" if isinstance(obj, tuple) else "$l": enc}
    if isinstance(obj, (np.integer, np.floating)):
        obj = obj.item()
    return {"$v": obj}


def _decode(spec, arrays: Dict[str, np.ndarray]):
    if "$a" in spec:
        return arrays[spec["$a"]]
    if "$d" in spec:
        return {k: _decode(v, arrays) for k, v in spec["$d"].items()}
    if "$l" in spec:
        return [_decode(v, arrays) for v in spec["$l"]]
    if "$t" in spec:
        return tuple(_decode(v, arrays) for v in spec["$t"])
    return spec["$v"]


# -- the per-stream durability handle ----------------------------------------

class StreamDurability:
    """Owns one durable stream's lanes, checkpoint manager, and cadence
    bookkeeping.  Installed as ``stream._durable`` by :func:`attach`;
    the engine hot paths call ``log_shard``/``log_arrive``/
    ``log_flush``/``log_hole`` (each from within the serialization
    domain that makes its lane single-writer: a ring's ordered commit,
    the stream lock, the committed frontier's lock)."""

    def __init__(self, stream, directory: str, *,
                 checkpoint_every_rows: Optional[int] = None,
                 keep: int = 3, segment_bytes: int = 1 << 20,
                 fsync: Optional[bool] = None) -> None:
        self.stream = stream
        self.directory = directory
        self.checkpoint_every_rows = checkpoint_every_rows
        self.keep = int(keep)
        if fsync is None:
            fsync = os.environ.get("REPRO_LOG_FSYNC", "0") == "1"
        os.makedirs(directory, exist_ok=True)
        self.lanes = _open_lanes(directory, stream.fields,
                                 stream.ts_field, stream.num_shards,
                                 segment_bytes=segment_bytes, fsync=fsync)
        self.manager = CheckpointManager(
            os.path.join(directory, "ckpt"), keep=self.keep)
        latest = self.manager.latest_step()
        self._step = latest if latest is not None else 0
        self._rows_at_ckpt = 0
        self.checkpoints = 0
        self.recovered = 0       # bumped by BigDawg.recover_stream
        self.last_recovery: Optional[Dict[str, Any]] = None
        self._ckpt_lock = threading.Lock()
        self._write_meta()

    # -- meta.json: everything needed to rebuild the stream fresh -------------
    def _write_meta(self) -> None:
        path = os.path.join(self.directory, "meta.json")
        if os.path.exists(path):
            return
        s = self.stream
        meta = {"kind": "stream", "name": s.name,
                "fields": list(s.fields),
                "ts_field": s.ts_field, "max_delay": s.max_delay,
                "idle_timeout": s.idle_timeout,
                "keep": self.keep,
                "checkpoint_every_rows": self.checkpoint_every_rows,
                "dead_letter": s._late_sink is not None,
                "shard_key": s.shard_key, "block_rows": s.block_rows,
                "engines": s.shard_engines(),
                "ring_capacity": s.rings[0].capacity,
                "rolling": s.rolling,
                # the *logical* registration capacity: ring capacities
                # are a ceil-division of it, so multiplying back would
                # inflate the figure and break the StreamSpec manifest
                # round-trip (spec ≡ from_manifest(meta))
                "capacity": (s.spec.capacity if s.spec is not None
                             else s.capacity)}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(tmp, path)

    # -- write-behind log hooks (called from engine.py) ------------------------
    def log_shard(self, shard: int, block: int, total: int,
                  payload: Dict[str, np.ndarray]) -> None:
        n = payload[SEQ_FIELD].shape[0]
        with trace.span("stream/log_append", stream=self.stream.name,
                        shard=shard, rows=n):
            self.lanes[f"shard{shard}"].append(KIND_SHARD, block,
                                               total, payload, n)
        self._count_rows(n)

    def log_arrive(self, cols: Dict[str, np.ndarray], n: int) -> None:
        with trace.span("stream/log_append", stream=self.stream.name,
                        rows=n):
            self.lanes["lane0"].append(KIND_ARRIVE, -1, n, cols, n)
        self._count_rows(n)

    def log_flush(self, target: float) -> None:
        self.lanes["lane0"].append(KIND_FLUSH, -1, 0, None, 0,
                                   target=target)

    def log_hole(self, block: int, total: int, abandoned: bool) -> None:
        """Seq block [block, block+total) will never fully publish; its
        ring slices that did publish are already logged on their lanes.
        ``abandoned``: the frontier reaped it (``blocks_abandoned``)."""
        self.lanes["holes"].append(KIND_HOLE, block, total, None, 0,
                                   target=float(abandoned))

    def _count_rows(self, n: int) -> None:
        metrics.counter("repro_stream_log_records_total",
                        "segment-log records written",
                        stream=self.stream.name).inc()
        metrics.counter("repro_stream_log_rows_total",
                        "data rows written to the segment log",
                        stream=self.stream.name).inc(n)

    def lane_lsns(self) -> Dict[str, int]:
        return {lane: log.next_lsn for lane, log in self.lanes.items()}

    def rows_logged(self) -> int:
        return sum(log.rows for log in self.lanes.values())

    # -- checkpoint ------------------------------------------------------------
    def maybe_checkpoint(self) -> bool:
        """Cadence hook (StreamRuntime.tick): checkpoint once
        ``checkpoint_every_rows`` data rows have been logged since the
        last one.  Async save — the tick never blocks on .npy I/O."""
        if self.checkpoint_every_rows is None:
            return False
        if (self.rows_logged() - self._rows_at_ckpt
                < self.checkpoint_every_rows):
            return False
        self.checkpoint(blocking=False)
        return True

    def checkpoint(self, blocking: bool = True) -> int:
        """Capture (state, lane positions, dead-letter sink) at one
        coherent instant and save through the CheckpointManager; then
        prune log segments no retained checkpoint needs."""
        with self._ckpt_lock, \
                trace.span("stream/checkpoint", stream=self.stream.name):
            crash_point("stream/checkpoint:begin")
            self.manager.wait()
            self._prune_wal()

            def capture():
                caps = {"lsns": self.lane_lsns(),
                        "rows_logged": self.rows_logged(),
                        "late_sink": None}
                sink = self.stream._late_sink
                if sink is not None:
                    caps["late_sink"] = sink.export_state()
                return caps

            state, caps = self.stream._checkpoint_snapshot(capture)
            payload = {"state": state, "lsns": caps["lsns"],
                       "late_sink": caps["late_sink"]}
            arrays: Dict[str, np.ndarray] = {}
            spec = _encode(payload, "a", arrays)
            flat = {_META_KEY: np.array(json.dumps(spec)), **arrays}
            self._step += 1
            self.manager.save(self._step, flat, blocking=blocking)
            self._rows_at_ckpt = caps["rows_logged"]
            self.checkpoints += 1
            metrics.counter("repro_stream_checkpoints_total",
                            "stream durability checkpoints",
                            stream=self.stream.name).inc()
            crash_point("stream/checkpoint:saved")
            if blocking:
                self._prune_wal()
            return self._step

    def _prune_wal(self) -> None:
        """Drop segments wholly below the minimum lane position across
        every retained (promoted) checkpoint — older segments can never
        be replayed again."""
        floors: Dict[str, int] = {}
        for step in self.manager.all_steps():
            lsns = _checkpoint_lsns(self.manager, step)
            if lsns is None:
                return                   # unreadable: prune nothing
            for lane, lsn in lsns.items():
                floors[lane] = min(floors.get(lane, lsn), lsn)
        if not floors:
            return
        for lane, log in self.lanes.items():
            log.prune_below(floors.get(lane, 0))
        crash_point("stream/checkpoint:pruned")

    # -- status ---------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        return {"directory": self.directory,
                "lanes": len(self.lanes),
                "log_records": sum(log.records
                                   for log in self.lanes.values()),
                "log_rows": self.rows_logged(),
                "log_bytes": sum(log.bytes
                                 for log in self.lanes.values()),
                "segments": sum(len(log._segments())
                                for log in self.lanes.values()),
                "checkpoints": self.checkpoints,
                "checkpoint_every_rows": self.checkpoint_every_rows,
                "last_checkpoint_step": self._step or None,
                "recovered": self.recovered,
                "last_recovery": self.last_recovery}

    def close(self) -> None:
        self.manager.wait()
        for log in self.lanes.values():
            log.close()


def _checkpoint_lsns(manager: CheckpointManager,
                     step: int) -> Optional[Dict[str, int]]:
    """The per-lane log positions of one checkpoint, read from its meta
    leaf only (no array loads)."""
    path = os.path.join(manager.directory, f"step_{step:08d}")
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        meta_file = manifest["leaves"][_META_KEY]["file"]
        spec = json.loads(str(np.load(os.path.join(path, meta_file))))
        # decode just the lsns subtree — the full payload holds $a array
        # refs we have not (and need not have) loaded
        return {k: int(v) for k, v in
                _decode(spec["$d"]["lsns"], {}).items()}
    except (OSError, KeyError, ValueError):
        return None


# -- attach / recover / replay ------------------------------------------------

def attach(stream, directory: str, *,
           checkpoint_every_rows: Optional[int] = None,
           keep: int = 3, segment_bytes: int = 1 << 20,
           fsync: Optional[bool] = None) -> StreamDurability:
    """Make ``stream`` durable: open (or create) its log directory and
    install the write-behind hook.  Idempotent per stream object."""
    if stream._durable is not None:
        return stream._durable
    durable = StreamDurability(
        stream, directory, checkpoint_every_rows=checkpoint_every_rows,
        keep=keep, segment_bytes=segment_bytes, fsync=fsync)
    stream._durable = durable
    return durable


@dataclasses.dataclass
class RecoveryResult:
    stream: Stream                    # detached: unregistered, no log hook
    late_sink: Optional[Stream]
    checkpoint_step: Optional[int]
    records_replayed: int
    rows_replayed: int
    seconds: float
    truncated_records: int            # cut as unrecoverable (torn/partial)


def recover(directory: str, *, repair: bool = True) -> RecoveryResult:
    """Rebuild the durable stream from ``directory``: latest checkpoint
    (or a fresh stream per ``meta.json``), then replay the log tail
    through the live ingest code paths.  With ``repair=True`` (the
    post-crash mode) torn tails and incompletely-logged blocks are
    physically truncated so the next recovery sees a consistent log;
    ``repair=False`` is the read-only mode ``replay(S)`` uses against a
    live stream's directory.

    The result's stream is detached (not registered, no durability
    hook) — ``BigDawg.recover_stream`` does both."""
    t0 = time.perf_counter()
    with open(os.path.join(directory, "meta.json")) as f:
        meta = json.load(f)
    manager = CheckpointManager(os.path.join(directory, "ckpt"),
                                keep=int(meta.get("keep", 3)))
    step = manager.latest_step()
    with trace.span("stream/replay", stream=meta["name"],
                    checkpoint=step if step is not None else -1):
        sink = None
        lsns: Dict[str, int] = {}
        if step is not None:
            flat = manager.restore_flat(step)
            spec = json.loads(str(flat.pop(_META_KEY)))
            payload = _decode(spec, flat)
            stream = Stream.from_state(payload["state"])
            if payload.get("late_sink") is not None:
                sink = Stream.from_state(payload["late_sink"])
            lsns = {k: int(v) for k, v in payload["lsns"].items()}
        else:
            stream = Stream(meta["name"], meta["fields"],
                            meta["ring_capacity"], rolling=meta["rolling"],
                            ts_field=meta["ts_field"],
                            max_delay=meta["max_delay"],
                            idle_timeout=meta["idle_timeout"],
                            shards=meta["engines"],
                            shard_key=meta["shard_key"],
                            block_rows=meta["block_rows"])
        if sink is None and meta.get("dead_letter"):
            sink = Stream(f"{meta['name']}.__late", meta["fields"],
                          meta["capacity"])
        stream._late_sink = sink

        lanes = _open_lanes(directory, meta["fields"], meta["ts_field"],
                            len(meta["engines"]))
        records = {lane: log.scan(lsns.get(lane, 0), repair=repair)
                   for lane, log in lanes.items()}
        if meta["ts_field"] is not None:
            replayed, rows = _replay_arrivals(stream, records["lane0"])
            cut = 0
        else:
            replayed, rows = _apply_blocks(stream, records)
            # cut: every lane record of a block at/after the frontier
            # (a crash landed between its ring commits, or between a
            # ring publish and its log append) — per lane a suffix
            cut = 0
            for lane, recs in records.items():
                bad = [r for r in recs if r.block >= stream.total_appended]
                cut += len(bad)
                if bad and repair:
                    lanes[lane].truncate_from(bad[0].lsn)
        for log in lanes.values():
            log.close()
    seconds = time.perf_counter() - t0
    metrics.counter("repro_stream_recoveries_total",
                    "stream recover() invocations",
                    stream=meta["name"]).inc()
    metrics.counter("repro_stream_replay_rows_total",
                    "rows re-applied from the segment log",
                    stream=meta["name"]).inc(rows)
    return RecoveryResult(stream=stream, late_sink=sink,
                          checkpoint_step=step,
                          records_replayed=replayed, rows_replayed=rows,
                          seconds=seconds, truncated_records=cut)


def _open_lanes(directory: str, fields, ts_field: Optional[str],
                rings: int, **log_kwargs) -> Dict[str, SegmentLog]:
    """A stream's log lanes: one ``shard{i}`` lane of ``SHARD`` records
    per ring plus the ``holes`` lane for a seq-ordered stream, one
    ``lane0`` of ``ARRIVE`` and ``FLUSH`` records for an event-time
    stream."""
    wal = os.path.join(directory, "wal")
    if ts_field is not None:
        return {"lane0": SegmentLog(os.path.join(wal, "lane0"),
                                    tuple(fields), **log_kwargs)}
    lanes = {f"shard{i}": SegmentLog(os.path.join(wal, f"shard{i}"),
                                     tuple(fields) + (SEQ_FIELD,),
                                     **log_kwargs)
             for i in range(rings)}
    lanes["holes"] = SegmentLog(os.path.join(wal, "holes"), (),
                                **log_kwargs)
    return lanes


def _replay_arrivals(stream: Stream, records: List[Record]
                     ) -> Tuple[int, int]:
    """Replay an event-time stream's lane in lsn order through its live
    ingest (arrival batches re-run late classification; punctuation
    re-applies the logged watermark).  Returns (records, rows)."""
    for rec in records:
        if rec.kind == KIND_ARRIVE:
            stream.append(rec.cols)
        else:
            stream.flush(rec.target)
    return len(records), sum(rec.nrows for rec in records)


def _apply_blocks(stream: Stream, records: Dict[str, List[Record]]
                  ) -> Tuple[int, int]:
    """Replay seq lanes (one per ring) by reassembling blocks: a block
    is applied only when the records across lanes account for every one
    of its rows — or a ``HOLE`` record says it never will, when the
    slices that did publish are applied — and only in contiguous seq
    order from the stream's frontier: the first incomplete block stops
    it.  Returns (records, rows) applied."""
    blocks: Dict[int, Dict[int, Record]] = {}
    holes: Dict[int, Record] = {}
    for lane, recs in records.items():
        if lane == "holes":
            holes.update((rec.block, rec) for rec in recs)
            continue
        ring = int(lane[len("shard"):])
        for rec in recs:
            blocks.setdefault(rec.block, {})[ring] = rec
    replayed = rows = 0
    while True:
        t = stream.total_appended
        parts, hole = blocks.get(t, {}), holes.get(t)
        logged = sum(r.nrows for r in parts.values())
        if hole is not None:
            total = hole.total
        elif parts and logged == next(iter(parts.values())).total:
            total = logged
        else:
            break
        stream._apply_block(t, total, {i: r.cols for i, r in parts.items()},
                            abandoned=hole is not None and hole.target > 0)
        replayed += len(parts) + (hole is not None)
        rows += logged
    return replayed, rows


# -- fingerprint: the recovered ≡ original equality ---------------------------

def fingerprint(stream: Stream) -> Dict[str, Any]:
    """A comparable digest of everything ``recovered ≡ original``
    promises: counters, watermarks, ring contents (exact bytes, in seq
    order), pending buffers, and the dead-letter sink.  Wall-clock-only
    state (append-time history, idle arrival stamps) is excluded."""
    import hashlib

    def sha(arrays) -> str:
        h = hashlib.sha256()
        for a in arrays:
            h.update(np.ascontiguousarray(a, np.float64).tobytes())
        return h.hexdigest()

    state = stream.export_state()
    out = {k: state[k] for k in (
        "name", "total_appended", "blocks_reserved", "rows_reserved",
        "blocks_abandoned", "watermark", "max_ts_seen", "min_ts_seen",
        "total_late", "shard_max_ts")}
    out["pending_rows"] = sum(b[stream.fields[0]].shape[0]
                              for b in state["pending"])
    out["pending"] = sha(b[f] for b in state["pending"]
                         for f in stream.fields)
    out["shards"] = [{"name": r["name"],
                      "rows": r["cols"][SEQ_FIELD].shape[0],
                      "total_appended": r["total_appended"],
                      "total_dropped": r["total_dropped"],
                      "evicted_ts": r["evicted_ts"],
                      "ring": sha(r["cols"][f] for f in r["fields"])}
                     for r in state["rings"]]
    if stream._late_sink is not None:
        out["late_sink"] = fingerprint(stream._late_sink)
    return out


# -- replica catch-up ---------------------------------------------------------

def catch_up(replica: Stream, durable: StreamDurability) -> Dict[str, Any]:
    """Bring a read replica (Migrator stream-route *copy* mode) up to
    date with its primary by replaying the primary's live segment log
    from the per-lane positions stored on the replica at copy time
    (``replica._replica_lsns``, captured inside
    ``_checkpoint_snapshot`` so state and log position agree exactly).

    Read-only against the log (``repair=False`` — a concurrent
    writer's half-flushed tail is skipped, never cut) and incremental:
    the replica's lane floors advance past every applied record, so
    repeated calls replay only the delta.  A seq block is applied only
    once every ring slice of it has been logged; an incomplete tail
    block stays pending until the next call."""
    t0 = time.perf_counter()
    floors: Dict[str, int] = dict(
        getattr(replica, "_replica_lsns", None) or {})
    with trace.span("stream/catch_up", stream=replica.name):
        records = {lane: log.scan(floors.get(lane, 0), repair=False)
                   for lane, log in durable.lanes.items()}
        if replica.ts_field is not None:
            replayed, rows = _replay_arrivals(replica, records["lane0"])
        else:
            replayed, rows = _apply_blocks(replica, records)
    for lane, recs in records.items():
        # the next floor: the lane's first unapplied record
        pending = [r.lsn for r in recs
                   if replica.ts_field is None
                   and r.block >= replica.total_appended]
        nxt = pending[0] if pending else (recs[-1].lsn + 1 if recs else 0)
        floors[lane] = max(floors.get(lane, 0), nxt)
    replica._replica_lsns = floors
    metrics.counter("repro_stream_replica_catchup_rows_total",
                    "rows applied to read replicas from the primary's "
                    "segment log",
                    stream=replica.name).inc(rows)
    return {"records": replayed, "rows": rows,
            "seconds": time.perf_counter() - t0, "lsns": dict(floors)}


# -- replay-as-loadgen --------------------------------------------------------

def replay_clone(stream) -> Dict[str, float]:
    """Rebuild the durable stream from its on-disk log into a detached
    clone (read-only scan — the live log is never repaired), timing the
    rebuild: the segment log doubling as a deterministic load
    generator.  Returns the stats row the BQL ``replay(S)`` op and the
    ``stream/replay_rate`` bench report: replayed records/rows,
    seconds, rows/sec, and whether the clone is bit-identical to the
    live stream right now (1.0 exactly when no ingest raced the
    replay)."""
    durable = stream._durable
    if durable is None:
        raise StreamException(
            f"stream {stream.name!r} has no durability attached "
            f"(register it with durability=<dir>)")
    result = recover(durable.directory, repair=False)
    identical = float(fingerprint(result.stream) == fingerprint(stream))
    rate = (result.rows_replayed / result.seconds
            if result.seconds > 0 else 0.0)
    return {"checkpoint_step": float(result.checkpoint_step or 0),
            "records": float(result.records_replayed),
            "rows": float(result.rows_replayed),
            "seconds": result.seconds,
            "rows_per_second": rate,
            "identical": identical}
