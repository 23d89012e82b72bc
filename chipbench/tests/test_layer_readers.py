"""The per-layer readers of the program's spans and counters, each on a
synthetic traced window: the value it reports, and None where the
window holds no work or nothing of what it reads (a program without
the span or counter)."""
import pytest

from chipbench import harness
from repro.obs.trace import SpanRecord

_ids = iter(range(1, 10**6))


def rec(name, start, duration, parent=None):
    return SpanRecord(name=name, trace_id="t", span_id=next(_ids),
                      parent_id=parent.span_id if parent else None,
                      start=start, duration=duration, thread_id=1,
                      thread_name="main")


def ticks_window():
    """Two ticks of 100 and 80 ms: gathers, device waits, a query and
    the front door's fan-out under them."""
    out = []
    for t0, length in ((0.0, 0.100), (0.2, 0.080)):
        tick = rec("stream/tick", t0, length)
        query = rec("stream/query", t0 + 0.001, 0.060, tick)
        out += [tick, query,
                rec("stream/gather", t0 + 0.002, 0.020, query),
                rec("device/wait", t0 + 0.030, 0.005, query),
                rec("serve/fanout", t0 + 0.070, 0.010, tick)]
    return out


def ctx(spans, work, registry=None):
    return {"spans": spans, "work": work, "registry": registry or {}}


@pytest.mark.parametrize("metric,window,want", [
    ("stream.gather_ms", ctx(ticks_window(), {"ticks": 2}), 20.0),
    ("device.wait.standing", ctx(ticks_window(), {"ticks": 2}), 5.0),
    # (100 - 60 - 10) and (80 - 60 - 10) ms of the ticks' own work
    ("stream.tick_self_ms", ctx(ticks_window(), {"ticks": 2}), 20.0),
    ("stream.h2d_mb", ctx([], {"ticks": 4},
                          {"repro_stream_h2d_bytes_total": 3e7}), 7.5),
    ("device.wait.batch",
     ctx([rec("device/wait", 0.0, 0.024), rec("device/wait", 0.1, 0.036),
          rec("executor/node", 0.0, 0.030)], {"queries": 2}), 30.0),
])
def test_reader_value(metric, window, want):
    assert harness.reader(metric).read(window) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "stream.gather_ms", "device.wait.standing", "stream.tick_self_ms",
    "stream.h2d_mb", "device.wait.batch"])
@pytest.mark.parametrize("window", [
    ctx(ticks_window(), {}, {"repro_stream_h2d_bytes_total": 3e7}),
    ctx([rec("planner/query", 0.0, 0.01)], {"ticks": 3, "queries": 3},
        {"repro_stream_tick_seconds": (3, 0.3)})],
    ids=["no-work", "nothing-to-read"])
def test_reader_is_silent(metric, window):
    assert harness.reader(metric).read(window) is None
