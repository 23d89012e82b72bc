"""What the compiled path serves in the ICU standing-query mix.

A tiny instance of the ``mimic2-icu`` layout — a 4-ring bed stream
key-hashed by bed, plus the one-ring ABP/ECG event-time pair — runs the
ten standing queries of the ICU traffic mix (two tenants on one front
door) under ``REPRO_QUERY_BACKEND=jit``.  Per tick, ``compile.stats()``
must count exactly the plans the compiler serves (``executions``) and
the sub-queries it hands to the interpreter by design
(``interpreted``): the multi-ring bed windows stay interpreted, every
one-ring window, ewindow and the join compile, and the bed aggregates
take the rolling plan.  In steady state that is 8 executions and 3
interpreted sub-queries per tick, the 3/11 behind the mix's
interpreted share."""
import numpy as np
import pytest

from repro.core.api import default_deployment
from repro.serve.frontdoor import FrontDoor
from repro.stream import compile as qc
from repro.stream.spec import EventTime, Sharding, StreamSpec

BEDS, HZ, SPB = 4, 125, 25          # 4 beds at 125 Hz, 200 ms batches
BED, ABP, ECG = "icu.beds", "mimic2v26.abp_stream", "mimic2v26.ecg_stream"
# (query, tenants): window sizes in rows — a bed-stream second is
# HZ * BEDS rows, a pair second HZ / 2 rows of the 62-row windows
QUERIES = [
    (f"bdstream(aggregate(window({BED}, 500), avg(abp)))",
     ("ward", "cardio")),
    (f"bdstream(aggregate(window({BED}, 500), max(abp)))", ("ward",)),
    (f"bdstream(window({BED}, 500))", ("ward",)),
    (f"bdstream(aggregate(window({BED}, 500, 250), max(abp)))",
     ("ward",)),
    (f"bdstream(window({ABP}, 62))", ("ward",)),
    (f"bdstream(aggregate(window({ABP}, 62, 31), max(abp)))", ("ward",)),
    (f"bdstream(window({ECG}, 62, 31))", ("cardio",)),
    (f"bdstream(ewindow({ECG}, 62))", ("cardio",)),
    (f"bdstream(aggregate(ewindow({ABP}, 62), avg(abp)))", ("cardio",)),
    (f"bdstream(join(ewindow({ABP}, 62), ewindow({ECG}, 62), on=ts,"
     f" tol=0.5))", ("cardio",)),
]
# (ticks, executions per tick, interpreted per tick), read off the
# program before the stream classes were unified: the bed aggregates
# execute once 500 bed rows exist (tick 4), the pair's queries once the
# 750-row watermark delay has passed (tick 32)
EXPECTED = [(4, 0, 3), (28, 2, 3), (12, 8, 3)]


@pytest.fixture(autouse=True)
def _jit_backend(monkeypatch):
    monkeypatch.setenv(qc.BACKEND_ENV, "jit")
    qc.reset_stats()
    yield
    qc.reset_stats()


def test_standing_mix_compiles_what_it_compiled():
    pytest.importorskip("jax")
    bd = default_deployment()
    beds = bd.register_stream("streamstore0", StreamSpec(
        BED, ("t", "bed", "abp"), capacity=2 * HZ * BEDS,
        sharding=Sharding(shards=4, shard_key="bed")))
    pair = [bd.register_stream("streamstore0", StreamSpec(
        name, ("ts", field), capacity=2 * HZ,
        event_time=EventTime("ts", max_delay=6 * HZ)))
        for name, field in ((ABP, "abp"), (ECG, "ecg"))]
    assert beds.num_shards == 4 and [s.num_shards for s in pair] == [1, 1]
    door = FrontDoor(bd, stream_engine="streamstore0",
                     max_queries_per_tenant=16, result_buffer=64)
    sessions, subs = {}, []
    for query, tenants in QUERIES:
        for tenant in tenants:
            if tenant not in sessions:
                sessions[tenant] = door.open_session(tenant)
            subs.append(sessions[tenant].subscribe(query))
    rng = np.random.default_rng(0)
    per_tick = []
    for k in range(sum(n for n, _, _ in EXPECTED)):
        t = k * SPB + np.repeat(np.arange(SPB), BEDS)
        bed = np.tile(np.arange(BEDS), SPB).astype(float)
        beds.append({"t": t.astype(float), "bed": bed,
                     "abp": 90 + 0.1 * bed + rng.standard_normal(t.size)})
        ts = k * SPB + np.arange(SPB, dtype=float)
        for stream, offset in zip(pair, (0.0, 0.25)):
            order = np.argsort(ts + rng.uniform(-2 * HZ, 2 * HZ, SPB),
                               kind="stable")
            stream.append({"ts": (ts + offset)[order],
                           stream.fields[1]: rng.standard_normal(SPB)})
        before = qc.stats()
        bd.streams.tick()
        after = qc.stats()
        for sub in subs:
            sub.poll()
        per_tick.append(tuple(after[key] - before[key] for key in
                              ("executions", "interpreted", "fallbacks")))
    expected = [(e, i, 0) for n, e, i in EXPECTED for _ in range(n)]
    assert per_tick == expected
