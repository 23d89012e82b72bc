"""Plain reference for the standing queries: numpy over the rows the
harness appended, in float64, with the semantics of the streaming
island (tumbling and sliding windows by row count over the ring,
event-time windows closed by the watermark, the banded interval join).

Copied from the checks of ``chip_smoke.py``; imports nothing of the
program.  ``compare`` turns one delivery and its reference into the
numbers that decide ``correct``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np


def flushed(batches, field: str, max_delay: float, final: bool = False):
    """The rows an event-time stream has released: every row at or below
    its watermark (max ts seen - max_delay), in ts order."""
    ts = np.concatenate([b["ts"] for b in batches])
    val = np.concatenate([b[field] for b in batches])
    wm = ts.max() if final else ts.max() - max_delay
    keep = ts <= wm
    order = np.argsort(ts[keep], kind="stable")
    return wm, {"ts": ts[keep][order], field: val[keep][order]}


def ewindow(wm: float, rows, span: float):
    """The latest event-time window [start, start + span) closed by the
    watermark ``wm``."""
    start = math.floor((wm - span) / span) * span
    sel = (rows["ts"] >= start) & (rows["ts"] < start + span)
    return {f: v[sel] for f, v in rows.items()}


def tumbling(rows, size: int):
    """The last complete tumbling window of ``size`` rows."""
    n = next(iter(rows.values())).shape[0]
    k = n // size - 1
    return {f: v[k * size:(k + 1) * size] for f, v in rows.items()}


def sliding(rows, size: int, slide: int):
    """Every sliding window of ``size`` rows, ``slide`` apart, over the
    rows still in the ring."""
    n = next(iter(rows.values())).shape[0]
    starts = range(0, n - size + 1, slide)
    return {f: np.stack([v[s:s + size] for s in starts])
            for f, v in rows.items()}


def join(left, right, tol: float):
    """The interval join on ``ts``: every (left, right) pair with
    ``|r.ts - l.ts| <= tol``, by left row, then right ts."""
    order = np.argsort(right["ts"], kind="stable")
    rs = {f: v[order] for f, v in right.items()}
    lo = np.searchsorted(rs["ts"], left["ts"] - tol, side="left")
    hi = np.searchsorted(rs["ts"], left["ts"] + tol, side="right")
    li = np.repeat(np.arange(left["ts"].shape[0]), hi - lo)
    ri = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)]
                        ) if li.shape[0] else np.zeros(0, np.int64)
    out = {f"l_{f}": v[li] for f, v in left.items()}
    out.update({f"r_{f}": v[ri] for f, v in rs.items()})
    out["dt"] = rs["ts"][ri] - left["ts"][li]
    return out


def ring(rows, capacity: int):
    """The newest ``capacity`` rows: what a ring of that size holds."""
    return {f: v[-capacity:] for f, v in rows.items()}


AGGS = {"avg": np.mean, "max": np.max, "min": np.min, "sum": np.sum,
        "count": lambda v: float(v.shape[0])}


def compare(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            exact: Tuple[str, ...]) -> Tuple[int, float]:
    """(exact mismatch, value error) of one delivery.

    The mismatch is 1 where the columns, a shape, or an ``exact`` column
    (timestamps, bed ids, row counts) differ, else 0.  The value error
    is the largest ``|got - ref| / |ref|`` over the other columns (0
    where the delivery already mismatched)."""
    if sorted(got) != sorted(ref):
        return 1, 0.0
    worst = 0.0
    for k, r in ref.items():
        g = np.asarray(got[k], np.float64)
        r = np.asarray(r, np.float64)
        if g.shape != r.shape:
            return 1, 0.0
        if k in exact:
            if not np.array_equal(g, r):
                return 1, 0.0
            continue
        if r.size:
            scale = np.maximum(np.abs(r), np.finfo(np.float32).tiny)
            worst = max(worst, float(np.max(np.abs(g - r) / scale)))
    return 0, worst
