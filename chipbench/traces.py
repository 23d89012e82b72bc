"""Reduction of a profiler trace to device busy time, idle gaps and the
device operations that took most time.

``reduce_events`` works on plain lists, so it is checked on synthetic
events; ``read_xplane`` pulls those lists out of the ``.xplane.pb`` the
JAX profiler writes.  Device operations are the events of the
``XLA Ops`` line of every ``/device:TPU`` plane; host spans are the
``bench/...`` annotations the harness writes with
``jax.profiler.TraceAnnotation`` on the host planes.  Both are read in
nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

HOST_PREFIX = "bench/"
# "%convert.49 = bf16[28,12,128,1536]{3,2,1,0:T(8,128)} convert(...)":
# an XLA Ops event is named by its whole HLO instruction
_HLO = re.compile(r"^(%?[\w.-]+) = \(?(\w+\[[^\]]*\])")


def op_name(hlo: str) -> str:
    """The instruction's name and result shape, without the operands."""
    m = _HLO.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals into disjoint ones, in order."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_events(device: Dict[str, List[Event]], host: List[Event],
                  window: Tuple[int, int], top: int = 10) -> Optional[Dict]:
    """Busy seconds (the union of operation intervals, averaged over the
    devices), the longest idle gaps on the first device, each named by
    the host span that covers most of it, and the ``top`` operations by
    summed device time.  None where no device operation was recorded."""
    w0, w1 = window
    if w1 <= w0 or not any(device.values()):
        return None
    busy, ops = [], collections.Counter()
    gaps: List[Tuple[int, int]] = []
    for i, name in enumerate(sorted(device)):
        spans = []
        for op, s, d in device[name]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                spans.append((a, b))
                ops[op] += (b - a) / 1e9
        merged = union(spans)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        if i == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"busy_s": sum(busy) / len(busy), "window_s": (w1 - w0) / 1e9,
            "device_ops": [[k, v] for k, v in ops.most_common(top)],
            "idle_gaps": [[_cover(host, a, b), (b - a) / 1e9]
                          for a, b in gaps[:top]]}


def _cover(host: List[Event], a: int, b: int) -> str:
    """The host span that overlaps [a, b) the most (innermost wins a
    tie), or ``host/other``."""
    best, best_len = "host/other", 0
    for name, s, d in host:
        ov = min(s + d, b) - max(s, a)
        if ov > best_len or (ov == best_len and ov > 0):
            best, best_len = name, ov
    return best


def read_xplane(directory: str) -> Tuple[Dict[str, List[Event]],
                                         List[Event]]:
    """(device operations by device plane, harness host spans) of the
    newest trace under ``directory``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return {}, []
    data = ProfileData.from_file(files[-1])
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.setdefault(plane.name, []).extend(
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return device, host
