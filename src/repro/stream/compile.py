"""Compiled standing-query path: streaming sub-plans lowered onto XLA.

The BQL interpreter evaluates every streaming expression with numpy on
the caller's thread, so per-tick standing queries are GIL-bound no
matter how concurrent the ingest side got (arXiv:1905.10336's point
that polystores need accelerator offload).  This module compiles the
streaming op family —

  window(S, n)             tumbling gather     (device dynamic-slice)
  window(S, n, s)          sliding gather      (one 2-D device gather
                                               replacing the Python
                                               stacking loop)
  ewindow(S, span[, s])    event-time gather   (host binary search for
                                               the bounds, device
                                               gather for the rows)
  aggregate(window(S,n),f) rolling aggregate   (lowered to the O(1)
                                               cumulative-ring lookup —
                                               already the optimal plan
                                               stage)
  aggregate(<window>, f)   windowed aggregate  (compiled gather feeding
                                               the data model's jnp
                                               reduction unchanged)
  join(W1, W2, on, tol)    banded interval join (device searchsorted +
                                               pair expansion over
                                               padded buckets)

— into jitted functions over the stream's exported ring arrays.  A
standing query compiles once per (stream, normalized sub-query) — the
streaming analog of the Planner's signature-keyed plan cache, and the
two compose: the PlanCache skips plan enumeration, this cache skips
re-lowering, and jax's jit cache keys the residual static shapes.

House invariant: the compiled path is **bit-identical** to the
interpreter.  Every lowering is exact by construction — gathers and
dynamic slices move bits, the join matcher is integer index math over
the same widened float64 keys the interpreter searches, the rolling
aggregate reuses the same cumulative-ring subtraction, and windowed
aggregates feed the identical jnp reduction the interpreter calls — and
every output passes through the same dtype canonicalization the
interpreter applies.  The jit-parity CI lane runs the property + event-time suites
under both backends and diffs results.

x64/platform config (the bayespec exemplar): stream rings are float64,
and jax downcasts to float32 by default, so compiled computation runs
inside a **scoped** ``jax.enable_x64`` context — exact float64 in the
kernels, zero config leakage into the rest of the process — and outputs
come back float64 and pass through the same host ``jnp.asarray`` the
interpreter applies to its float64 numpy results (a device-side
float64 -> float32 convert would flush subnormals to zero, which the
host cast keeps).  This module is the only place allowed to touch jax
config (ruff TID251 bans ``jax.config.update`` everywhere else;
``jax_enable_x64`` below is the explicit process-wide switch for
operators who want global x64, and ``use_compile_cache`` the one
entry points call to keep compiled programs across processes).

Backend selection: ``REPRO_QUERY_BACKEND=interpreter`` (default) or
``jit``, read per query so tests can flip it per-case.  Queries outside
the family stay on the interpreter by design (the ``interpreted``
counter); family queries that cannot compile — jax absent, non-finite
join keys — fall back and are counted in ``stats()`` (fed to the
Monitor every tick and surfaced by ``admin.status()``).
"""
from __future__ import annotations

import functools
import os
import pathlib
import re
import threading
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from repro.core import datamodel as dm
from repro.obs import metrics, trace
from repro.stream.engine import (_COMBINABLE_AGGS, ShardedStream, Stream,
                                 StreamException, _latest_closed_ewindow,
                                 to_device)

try:                                         # gate: jax may be absent
    import jax
    import jax.numpy as jnp
    JAX_AVAILABLE = True
except ModuleNotFoundError as exc:           # any other import error raises
    if exc.name != "jax":
        raise
    jax = jnp = None                         # type: ignore
    JAX_AVAILABLE = False

BACKEND_ENV = "REPRO_QUERY_BACKEND"
BACKENDS = ("interpreter", "jit")

# -- lifetime counters (reset via reset_stats; surfaced through Monitor) ----
_STATS_LOCK = threading.Lock()
_STATS: Dict[str, int] = {}
_FALLBACK_REASONS: Dict[str, int] = {}


def _reset_locked() -> None:
    _STATS.clear()
    _STATS.update(compiles=0, cache_hits=0, executions=0,
                  fallbacks=0, interpreted=0)
    _FALLBACK_REASONS.clear()


_reset_locked()


def backend() -> str:
    """The active query backend (env-driven, read per query)."""
    value = os.environ.get(BACKEND_ENV, "interpreter").strip().lower()
    return value if value in BACKENDS else "interpreter"


def stats() -> Dict[str, Any]:
    """Compiled-path health: plan compiles vs cache hits, jitted
    executions, interpreter fallbacks (with reasons), and queries the
    interpreter serves by design (ops outside the compiled family)."""
    with _STATS_LOCK:
        out: Dict[str, Any] = dict(_STATS)
        out["backend"] = backend()
        out["jax_available"] = JAX_AVAILABLE
        out["fallback_reasons"] = dict(_FALLBACK_REASONS)
        return out


def reset_stats() -> None:
    with _STATS_LOCK:
        _reset_locked()


def _bump(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] += n
    metrics.counter("repro_compile_events_total",
                    "compiled query path events (compiles, cache hits, "
                    "executions, by-design interpreted)",
                    event=key).inc(n)


def _fallback(reason: str) -> None:
    with _STATS_LOCK:
        _STATS["fallbacks"] += 1
        _FALLBACK_REASONS[reason] = _FALLBACK_REASONS.get(reason, 0) + 1
    metrics.counter("repro_compile_fallbacks_total",
                    "compiled plans that fell back to the interpreter",
                    reason=reason).inc()


# -- explicit process-wide config switches (operator-facing; the per-tick
# path never calls these — it uses the scoped x64 context instead) ----------
def jax_enable_x64(use_x64: Optional[bool] = None) -> None:
    """Flip jax's global float64 mode, honoring ``JAX_ENABLE_X64`` when
    no explicit value is given (the bayespec idiom).  Affects the whole
    process — every jnp array created afterwards defaults to 64-bit."""
    if not JAX_AVAILABLE:
        return
    if use_x64 is None:
        use_x64 = bool(int(os.environ.get("JAX_ENABLE_X64", "0")))
    jax.config.update("jax_enable_x64", bool(use_x64))


def use_compile_cache() -> str:
    """Keep jax's compiled programs in a persistent cache, for entry
    points only (never called at import).  ``JAX_COMPILATION_CACHE_DIR``
    wins when set — jax reads it itself and nothing is overridden;
    otherwise the cache is ``<repo>/.jax_cache``, a fixed path because
    the path is part of every entry's key.  Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _pow2(n: int) -> int:
    """Static-shape bucket: next power of two >= max(n, 16), so varying
    data sizes re-trace the jitted functions O(log n) times, not O(n)."""
    b = 16
    while b < n:
        b <<= 1
    return b


# -- jitted primitives ------------------------------------------------------
# All of these trace under the scoped x64 context (float64 in, float64
# out, exact); the host canonicalizes the dtype like the interpreter.

@functools.partial(jax.jit if JAX_AVAILABLE else lambda f, **k: f,
                   static_argnames=("size",))
def _jit_tumbling(cols, off, size):
    """(F, capacity) ordered ring -> (F, size) window at offset ``off``
    (always fully in bounds: the eviction check ran on the host)."""
    return jax.lax.dynamic_slice(cols, (0, off), (cols.shape[0], size))


@functools.partial(jax.jit if JAX_AVAILABLE else lambda f, **k: f,
                   static_argnames=("size", "slide", "max_windows"))
def _jit_sliding(cols, size, slide, max_windows):
    """(F, capacity) ordered ring -> (F, max_windows, size) stacked
    sliding windows — replacing the interpreter's Python stacking loop.
    Every window start is static (``max_windows`` keeps the last slice
    inside the ring by construction), so XLA lowers the stack of slices
    to straight copies — no per-element gather index math.  Windows
    past the live count hold garbage the host slices away."""
    wins = [jax.lax.slice_in_dim(cols, i * slide, i * slide + size,
                                 axis=1) for i in range(max_windows)]
    return jnp.stack(wins, axis=1)


@functools.partial(jax.jit if JAX_AVAILABLE else lambda f, **k: f,
                   static_argnames=("length",))
def _jit_rows(cols, off, length):
    """(F, capacity) -> (F, length) rows starting at ``off`` — the
    ewindow gather, clip-indexed so the static padded length never
    reads out of bounds; the host slices the live prefix."""
    idx = off + jnp.arange(length, dtype=jnp.int32)
    idx = jnp.clip(idx, 0, cols.shape[1] - 1)
    return cols[:, idx]


@functools.partial(jax.jit if JAX_AVAILABLE else lambda f, **k: f)
def _jit_join_bounds(lt, rt, tol):
    """Per-left-row match bounds against the sorted right keys.

    Both key arrays are float64 (widened exactly like the interpreter's
    ``np.asarray(v, np.float64)``) padded with +inf, so the stable sort
    parks padding at the tail and real searches never reach it.  jax's
    searchsorted/stable-argsort match numpy's bit for bit (the parity
    suite pins this), so (lo, hi, order) equal the interpreter's."""
    order = jnp.argsort(rt, stable=True)
    rs = rt[order]
    lo = jnp.searchsorted(rs, lt - tol, side="left")
    hi = jnp.searchsorted(rs, lt + tol, side="right")
    return lo, hi, order


@functools.partial(jax.jit if JAX_AVAILABLE else lambda f, **k: f,
                   static_argnames=("pairs",))
def _jit_join_gather(lcols, rcols, lt, rt, lo, cum, order, pairs):
    """Expand (lo, counts) into the interpreter's pair list — ordered by
    left row, then right timestamp — and gather both sides plus
    ``dt = r.on - l.on``.  Pure integer index math and one float64
    subtraction of the same operands the interpreter subtracts, so the
    result is bitwise identical; pad pairs are clipped garbage the host
    slices away."""
    k = jnp.arange(pairs, dtype=cum.dtype)
    row = jnp.searchsorted(cum, k, side="right")
    row = jnp.clip(row, 0, lt.shape[0] - 1)
    prev = jnp.where(row > 0, cum[jnp.maximum(row - 1, 0)], 0)
    slot = jnp.clip(lo[row] + (k - prev), 0, order.shape[0] - 1)
    ri = order[slot]
    return lcols[:, row], rcols[:, ri], rt[ri] - lt[row]


# -- query parsing (the compiled op family) ---------------------------------
_WINDOW_RE = re.compile(
    r"^window\(\s*([\w\.]+)\s*,\s*(\d+)\s*(?:,\s*(\d+)\s*)?\)$",
    re.IGNORECASE)
_EWINDOW_RE = re.compile(
    r"^ewindow\(\s*([\w\.]+)\s*,\s*([\d\.eE+-]+)\s*"
    r"(?:,\s*([\d\.eE+-]+)\s*)?\)$", re.IGNORECASE)
_AGG_RE = re.compile(r"^(count|sum|avg|min|max)\(\s*(\*|[\w\.]+)\s*\)$",
                     re.IGNORECASE)
_KWARG_RE = re.compile(r"^(\w+)\s*=\s*(.+)$")
_TOKEN_RE = re.compile(r"[\w\.]+")

# one compiled-plan dict per live stream object (dies with the stream);
# inside, plans key on the normalized sub-query text — the streaming
# analog of the Planner's signature key
_PLANS: "weakref.WeakValueDictionary[int, Any]" = \
    weakref.WeakValueDictionary()
_PLAN_CACHE: Dict[int, Dict[str, "CompiledStreamQuery"]] = {}
_PLAN_LOCK = threading.Lock()


def _normalize(q: str) -> str:
    return re.sub(r"\s+", "", q).lower()


def _plan_cache_for(stream) -> Dict[str, "CompiledStreamQuery"]:
    """The stream's compiled-plan dict, garbage-collected with it."""
    key = id(stream)
    with _PLAN_LOCK:
        if _PLANS.get(key) is not stream:
            # new stream (or an id reused by a successor): fresh plans
            _PLANS[key] = stream
            _PLAN_CACHE[key] = {}
            for dead in [k for k in _PLAN_CACHE if k not in _PLANS]:
                del _PLAN_CACHE[dead]
        return _PLAN_CACHE[key]


class Uncompilable(Exception):
    """The expression is outside the compiled op family — the
    interpreter serves it by design (not a fallback)."""


class CompiledStreamQuery:
    """One lowered streaming sub-plan bound to its stream object.

    ``execute()`` runs per tick: the host stage takes the stream lock
    only to export the point-in-time ring arrays (and resolve window
    bounds with the interpreter's own arithmetic, so every data-
    dependent StreamException — window not complete, evicted, watermark
    not started — raises identically), then the jitted stage runs
    outside every lock and off the GIL."""

    def __init__(self, kind: str, run: Callable[[], Any]) -> None:
        self.kind = kind
        self._run = run

    def execute(self) -> Any:
        return self._run()


# -- window lowerings -------------------------------------------------------
def _export_stacked(stream: Stream) -> Tuple[int, int, np.ndarray]:
    """(total_appended, count, (F, capacity) zero-padded oldest-first
    rows) — one point-in-time ring export; the lock is held only for
    the gather copy, exactly like the interpreter's ``_ordered`` reads."""
    with trace.span("stream/gather", stream=stream.name,
                    path="compiled") as sp, stream._lock:
        count = stream._count
        total = stream.total_appended
        out = np.zeros((len(stream.fields), stream.capacity), np.float64)
        for j, f in enumerate(stream.fields):
            out[j, :count] = stream._ordered(f)
        sp.set(rows=count)
    return total, count, out


def _compile_window(stream, size: int,
                    slide: Optional[int]) -> CompiledStreamQuery:
    if not isinstance(stream, Stream):
        raise Uncompilable("sharded window gathers stay interpreted")
    if size <= 0 or (slide is not None and slide <= 0):
        raise Uncompilable("non-positive window size/slide")
    fields = stream.fields

    if slide is None:
        def run() -> dm.ArrayObject:
            total, count, stacked = _export_stacked(stream)
            first_seq = total - count
            k = total // size - 1
            if k < 0:
                raise StreamException(
                    f"stream {stream.name!r}: no complete window of "
                    f"size {size} yet ({total} rows)")
            s = k * size
            if s < first_seq:
                raise StreamException(
                    f"stream {stream.name!r}: window [{s},{s + size}) "
                    f"already evicted (buffer starts at {first_seq})")
            with jax.enable_x64(True):
                out = _jit_tumbling(to_device(stacked), s - first_seq,
                                    size=size)
            # zero-copy np view, numpy slicing, one device_put per
            # field: eager jax slicing on the host path costs ~0.5ms
            # *per op* in dispatch, which would swamp the jitted gather
            arr = np.asarray(trace.device_wait(out))
            return dm.ArrayObject(
                {f: to_device(arr[j]) for j, f in enumerate(fields)},
                ("tick",))

        return CompiledStreamQuery("window", run)

    max_windows = (stream.capacity - size) // slide + 1
    if max_windows < 1:
        raise Uncompilable("window larger than ring capacity")

    def run_sliding() -> dm.ArrayObject:
        _, count, stacked = _export_stacked(stream)
        if count < size:
            raise StreamException(
                f"stream {stream.name!r}: {count} rows < window "
                f"size {size}")
        num = (count - size) // slide + 1
        with jax.enable_x64(True):
            out = _jit_sliding(to_device(stacked), size=size, slide=slide,
                               max_windows=max_windows)
        arr = np.asarray(trace.device_wait(out))  # zero-copy; slice in numpy
        return dm.ArrayObject(
            {f: to_device(arr[j, :num]) for j, f in enumerate(fields)},
            ("window", "tick"))

    return CompiledStreamQuery("window", run_sliding)


def _compile_ewindow(stream, span: float,
                     slide: Optional[float]) -> CompiledStreamQuery:
    if not isinstance(stream, Stream):
        raise Uncompilable("sharded ewindow gathers stay interpreted")
    if stream.ts_field is None:
        raise Uncompilable("ewindow over a stream with no ts_field")
    fields = stream.fields

    def run() -> dm.ArrayObject:
        start, end = _latest_closed_ewindow(stream, span, slide)
        with trace.span("stream/gather", stream=stream.name,
                        path="compiled") as sp, stream._lock:
            if start <= stream._evicted_ts:
                raise StreamException(
                    f"stream {stream.name!r}: ewindow [{start},{end}) "
                    f"already evicted (rows up to ts "
                    f"{stream._evicted_ts} overwritten)")
            a, b = stream._seq_bounds_locked(stream.ts_field, start, end)
            count = stream._count
            stacked = np.zeros((len(fields), stream.capacity),
                               np.float64)
            for j, f in enumerate(fields):
                stacked[j, :count] = stream._ordered(f)
            sp.set(rows=count)
        m = b - a
        with jax.enable_x64(True):
            out = _jit_rows(to_device(stacked), a,
                            length=_pow2(max(m, 1)))
        arr = np.asarray(trace.device_wait(out))  # zero-copy; slice in numpy
        return dm.ArrayObject(
            {f: to_device(arr[j, :m]) for j, f in enumerate(fields)},
            ("tick",))

    return CompiledStreamQuery("ewindow", run)


# -- aggregate lowerings ----------------------------------------------------
def _compile_aggregate(engine, expr: str, fn: str,
                       target: str) -> CompiledStreamQuery:
    win = _WINDOW_RE.match(expr)
    if win and win.group(3) is None:
        stream = _get_stream(engine, win.group(1))
        size = int(win.group(2))
        field = stream.fields[0] if target == "*" else target
        if fn not in _COMBINABLE_AGGS or field not in stream.fields:
            raise Uncompilable("non-rolling tumbling aggregate")
        if size <= 0:
            raise Uncompilable("non-positive window size")

        # rolling fast path: lowered to the O(1) cumulative-ring lookup
        # (already the optimal plan stage — identical memo, identical
        # value; sum/avg are order-sensitive, so no device reduction
        # could match them bit for bit)
        def run_rolling() -> dm.ArrayObject:
            value = stream.window_aggregate(size, fn, field)
            return dm.ArrayObject(
                {f"{fn}_{field}": to_device([value])}, ("i",))

        return CompiledStreamQuery("rolling", run_rolling)

    # windowed aggregate: compiled gather + the data model's own jnp
    # reduction (the interpreter's exact code path over bit-identical
    # window attrs, so the reduction order cannot diverge)
    window_plan = _compile_expr(engine, expr)

    def run() -> dm.ArrayObject:
        value = window_plan.execute()
        field = target
        if field == "*":
            field = next(iter(value.attrs))
        return value.aggregate(fn, field)

    return CompiledStreamQuery("aggregate", run)


# -- join lowering ----------------------------------------------------------
def _operand(engine, expr: str) -> Callable[[], dm.ArrayObject]:
    """A join operand evaluator: the compiled gather when the operand
    is in the family, else the interpreter's (sharded ewindows, bare
    snapshots — their host gathers are the lowering either way; the
    jitted matcher still runs on the result)."""
    try:
        plan = _compile_expr(engine, expr)
        return plan.execute
    except Uncompilable:
        pass

    def run() -> dm.ArrayObject:
        from repro.stream import shim
        return shim._as_window(shim.execute_stream(engine, expr))

    return run


def _compile_join(engine, left_expr: str, right_expr: str,
                  on: str, tol: float) -> CompiledStreamQuery:
    left_eval = _operand(engine, left_expr)
    right_eval = _operand(engine, right_expr)

    def run() -> dm.Table:
        from repro.stream import shim
        bands = shim._colocated_bands(engine, left_expr, right_expr)
        left = left_eval()
        right = right_eval()
        # the interpreter's exact operand widening + validation order
        la = {f: np.asarray(v, np.float64)
              for f, v in left.attrs.items()}
        ra = {f: np.asarray(v, np.float64)
              for f, v in right.attrs.items()}
        if on not in la or on not in ra:
            raise StreamException(
                f"join on={on!r}: both windows need that attribute "
                f"(have {sorted(la)} and {sorted(ra)})")
        t = float(tol)
        if t < 0:
            raise StreamException(f"join tol must be >= 0, got {t}")
        lt, rt = la[on], ra[on]
        if not (np.isfinite(lt).all() and np.isfinite(rt).all()):
            # +inf padding would collide with real keys; the numpy
            # interpreter handles these, so hand the query back
            raise Uncompilable("non-finite join keys")
        nl, nr = lt.shape[0], rt.shape[0]
        # the banded decomposition is bit-identical to the full join
        # (interval_join's contract), so one compiled matcher serves
        # both; only the partial-join accounting follows the bands
        bands_eff = max(1, min(int(bands), nl or 1))
        if nl == 0 or nr == 0:
            l_out = np.zeros((len(la), 0), np.float64)
            r_out = np.zeros((len(ra), 0), np.float64)
            dt = np.zeros(0, np.float64)
        else:
            lb, rb = _pow2(nl), _pow2(nr)
            lt_pad = np.full(lb, np.inf)
            lt_pad[:nl] = lt
            rt_pad = np.full(rb, np.inf)
            rt_pad[:nr] = rt
            lcols = np.zeros((len(la), lb), np.float64)
            for j, f in enumerate(la):
                lcols[j, :nl] = la[f]
            rcols = np.zeros((len(ra), rb), np.float64)
            for j, f in enumerate(ra):
                rcols[j, :nr] = ra[f]
            with jax.enable_x64(True):
                lt_dev, rt_dev = to_device(lt_pad), to_device(rt_pad)
                lo, hi, order = _jit_join_bounds(lt_dev, rt_dev, t)
                trace.device_wait((lo, hi))
                # zero-copy np views + numpy slicing (eager jax host
                # slices cost ~0.5ms/op in dispatch)
                lo_np = np.asarray(lo)[:nl]
                counts = np.asarray(hi)[:nl] - lo_np
                cum = np.cumsum(counts)
                pairs = int(cum[-1]) if nl else 0
                if pairs == 0:
                    l_out = np.zeros((len(la), 0), np.float64)
                    r_out = np.zeros((len(ra), 0), np.float64)
                    dt = np.zeros(0, np.float64)
                else:
                    l_dev, r_dev, dt_dev = _jit_join_gather(
                        to_device(lcols), to_device(rcols), lt_dev, rt_dev,
                        to_device(lo_np), to_device(cum), order,
                        pairs=_pow2(pairs))
                    trace.device_wait((l_dev, r_dev, dt_dev))
                    l_out = np.asarray(l_dev)[:, :pairs]
                    r_out = np.asarray(r_dev)[:, :pairs]
                    dt = np.asarray(dt_dev)[:pairs]
        if bands_eff > 1:
            shim.JOIN_STATS["partial_joins"] += 1
            metrics.counter("repro_stream_joins_total",
                            "interval joins executed",
                            kind="partial").inc()
        shim.JOIN_STATS["joins"] += 1
        metrics.counter("repro_stream_joins_total",
                        "interval joins executed", kind="full").inc()
        cols = {}
        for j, f in enumerate(la):
            cols[f"l_{f}"] = to_device(l_out[j])
        for j, f in enumerate(ra):
            cols[f"r_{f}"] = to_device(r_out[j])
        cols["dt"] = to_device(dt)
        return dm.Table(cols)

    return CompiledStreamQuery("join", run)


# -- plan builder -----------------------------------------------------------
def _get_stream(engine, name: str):
    from repro.stream import shim
    return shim._get_stream(engine, name)


def _compile_expr(engine, query: str) -> CompiledStreamQuery:
    """Lower one streaming expression, or raise Uncompilable when the
    op is outside the compiled family."""
    from repro.stream import shim
    q = query.strip()
    m = re.match(r"^(\w+)\s*\(", q)
    if not m:
        raise Uncompilable("bare snapshot stays interpreted")
    fn = m.group(1).lower()
    body, _ = shim._balanced(q[m.end() - 1:])
    args = shim._split_args(body)
    if fn == "window":
        w = _WINDOW_RE.match(q)
        if not w:
            raise Uncompilable("unparsed window arguments")
        return _compile_window(
            _get_stream(engine, w.group(1)), int(w.group(2)),
            int(w.group(3)) if w.group(3) else None)
    if fn == "ewindow":
        e = _EWINDOW_RE.match(q)
        if not e:
            raise Uncompilable("unparsed ewindow arguments")
        try:
            span = float(e.group(2))
            slide = float(e.group(3)) if e.group(3) else None
        except ValueError:
            raise Uncompilable("unparsed ewindow bounds") from None
        return _compile_ewindow(_get_stream(engine, e.group(1)),
                                span, slide)
    if fn == "aggregate":
        if len(args) != 2:
            raise Uncompilable("malformed aggregate")
        agg = _AGG_RE.match(args[1].strip())
        if not agg:
            raise Uncompilable("malformed aggregate function")
        return _compile_aggregate(engine, args[0].strip(),
                                  agg.group(1).lower(), agg.group(2))
    if fn == "join":
        if len(args) < 2:
            raise Uncompilable("malformed join")
        on, tol = "ts", 0.0
        for extra in args[2:]:
            kw = _KWARG_RE.match(extra.strip())
            if not kw or kw.group(1).lower() not in ("on", "tol"):
                raise Uncompilable("unknown join argument")
            if kw.group(1).lower() == "on":
                on = kw.group(2).strip()
            else:
                try:
                    tol = float(kw.group(2))
                except ValueError:
                    raise Uncompilable("unparsed join tol") from None
        return _compile_join(engine, args[0].strip(), args[1].strip(),
                             on, tol)
    raise Uncompilable(f"{fn} stays interpreted")


def _plan_anchor(engine, query: str):
    """The stream object anchoring the compiled-plan cache: the first
    token of the expression that resolves to a live stream.  Plans die
    with their stream, so a re-registered stream of the same name
    compiles fresh plans against the new ring."""
    for tok in _TOKEN_RE.findall(query):
        try:
            obj = engine.get(tok)
        except Exception:                    # noqa: BLE001 — not a name
            continue
        if isinstance(obj, (Stream, ShardedStream)):
            return obj
    return None


def maybe_execute(engine, query: str) -> Tuple[bool, Any]:
    """The shim's jit dispatch hook: under ``REPRO_QUERY_BACKEND=jit``
    try the compiled path.  Returns ``(True, value)`` when the compiled
    plan served the query, ``(False, None)`` when the interpreter
    should (op outside the family, jax missing, or a compile/runtime
    fallback — the latter counted).  Data-dependent StreamExceptions
    propagate exactly as the interpreter raises them."""
    if backend() != "jit":
        return False, None
    if not JAX_AVAILABLE:
        _fallback("jax_unavailable")
        return False, None
    key = _normalize(query)
    try:
        with trace.span("compile/plan") as sp:
            anchor = _plan_anchor(engine, query)
            if anchor is None:
                _bump("interpreted")
                return False, None
            cache = _plan_cache_for(anchor)
            plan = cache.get(key)
            if plan is None:
                plan = _compile_expr(engine, query)
                cache[key] = plan
                _bump("compiles")
                sp.set(cache_hit=False, op=plan.kind)
            else:
                _bump("cache_hits")
                sp.set(cache_hit=True, op=plan.kind)
    except Uncompilable:
        _bump("interpreted")
        return False, None
    except StreamException:
        raise
    except Exception as exc:                 # noqa: BLE001 — fall back
        _fallback(type(exc).__name__)
        return False, None
    try:
        with trace.span("compile/execute", op=plan.kind):
            value = plan.execute()
    except Uncompilable as exc:
        # the plan compiled but this tick's *data* defeated it (e.g.
        # non-finite join keys): a real fallback, not a by-design skip
        _fallback(str(exc) or "uncompilable")
        return False, None
    except StreamException:
        raise
    except Exception as exc:                 # noqa: BLE001 — fall back
        _fallback(type(exc).__name__)
        return False, None
    _bump("executions")
    return True, value
