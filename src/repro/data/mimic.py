"""Synthetic MIMIC-II-style dataset (paper §IV): the real MIMIC II database
is access-restricted, so we generate schema-compatible synthetic data —
patient history into the relational engine (PostgreSQL analog), physiologic
waveforms into the array engine (SciDB analog), free-form text into the KV
engine (Accumulo analog) — exactly the default placement of the v0.1
release scripts.

``stream_mimic_waveforms`` is the *live* counterpart: physiologic
waveforms arrive continuously in the real workload, so it feeds the same
synthetic signal batch-by-batch into the streaming island (paper §III's
S-Store member; see ``repro.stream``), ticking the standing-query runtime
after every batch.  ``stream_mimic_paired_waveforms`` adds the
cross-stream event-time workload: two jittered, out-of-order waveform
feeds (ABP + ECG) over a shared ``ts`` axis, for watermarked windows and
interval joins.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np
import jax.numpy as jnp

from repro.core import datamodel as dm
from repro.core.api import BigDawg


def load_mimic_demo(bd: BigDawg, *, num_patients: int = 256,
                    num_orders: int = 1024, wave_len: int = 4096,
                    num_logs: int = 64, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)

    # -- patient history -> relational engine (hoststore0) -------------------
    subject_id = np.arange(num_patients)
    d_patients = dm.Table({
        "subject_id": jnp.asarray(subject_id),
        "sex": jnp.asarray(rng.integers(0, 2, num_patients)),      # 0=F,1=M
        "dob_year": jnp.asarray(rng.integers(1930, 2000, num_patients)),
        "hospital_expire_flg": jnp.asarray(
            rng.integers(0, 2, num_patients)),
    })
    bd.register_object("hoststore0", "mimic2v26.d_patients", d_patients,
                       fields=tuple(d_patients.fields))

    poe_order = dm.Table({
        "poe_id": jnp.asarray(np.arange(num_orders)),
        "subject_id": jnp.asarray(
            rng.integers(0, num_patients, num_orders)),
        "icustay_id": jnp.asarray(rng.integers(0, 512, num_orders)),
        "dose": jnp.asarray(rng.uniform(0.5, 50.0, num_orders)),
    })
    bd.register_object("hoststore0", "mimic2v26.poe_order", poe_order,
                       fields=tuple(poe_order.fields))
    # replicate onto the second relational engine (paper ships mimic2_copy)
    bd.register_object("hoststore1", "mimic2v26.poe_order", poe_order,
                       fields=tuple(poe_order.fields))

    # -- physiologic waveforms -> array engine (densehbm0) -------------------
    t = np.arange(wave_len, dtype=np.float64)
    signal = (np.sin(2 * np.pi * t / 360.0)[None, :]
              * rng.uniform(0.5, 2.0, (8, 1))
              + 0.05 * rng.standard_normal((8, wave_len)))
    waveform = dm.ArrayObject(
        attrs={"signal": jnp.asarray(signal)},
        dim_names=("lead", "tick"))
    bd.register_object("densehbm0", "mimic2v26.waveform", waveform,
                       fields=("signal",))

    myarray = dm.ArrayObject(
        attrs={"val": jnp.asarray(rng.standard_normal(256))},
        dim_names=("dim1",))
    bd.register_object("densehbm0", "myarray", myarray, fields=("val",))

    # -- free-form text -> KV engine (kvstore0) ------------------------------
    keys, values = [], []
    for i in range(num_logs):
        keys.append((f"r_{i:04d}", "note", "text"))
        values.append(f"synthetic clinical note {i}: pt stable, "
                      f"hr={int(rng.integers(50, 120))}")
    bd.register_object("kvstore0", "mimic_logs", dm.KVTable(keys, values),
                       fields=("row", "colfam", "colqual", "value"))


def stream_mimic_waveforms(bd: BigDawg, *, batch_rows: int = 64,
                           num_batches: int = 32, capacity: int = 8192,
                           seed: int = 0,
                           name: str = "mimic2v26.waveform_stream",
                           engine_name: str = "streamstore0",
                           tick: bool = True, shards: int = 1,
                           shard_key: str = None,
                           num_engines: int = None) -> Iterator[Dict]:
    """Live MIMIC waveform feed: appends synthetic physiologic batches to
    a ring-buffer stream on the streaming island, one batch per
    iteration, advancing the continuous-query runtime after each.

    The signal is the same deterministic sine+noise family as
    ``load_mimic_demo``'s batch waveform, phased by the stream's global
    sequence number so a resumed feed continues the waveform seamlessly.
    With ``shards > 1`` the stream is hash-partitioned across multiple
    StreamEngines (scatter appends, seq-ordered gathers — results stay
    bit-identical to the unsharded feed).  Yields a per-batch dict with
    append counts and the standing-query responses that ran on that tick.
    """
    rng = np.random.default_rng(seed)
    engine = bd.engines[engine_name]
    if not engine.has(name):
        bd.register_stream(engine_name, name, ("signal", "hr"), capacity,
                           shards=shards, shard_key=shard_key,
                           num_engines=num_engines)
    stream = engine.get(name)
    for b in range(num_batches):
        t = stream.total_appended + np.arange(batch_rows,
                                              dtype=np.float64)
        signal = (np.sin(2 * np.pi * t / 360.0)
                  + 0.05 * rng.standard_normal(batch_rows))
        hr = 75.0 + 10.0 * np.sin(2 * np.pi * t / 3600.0) \
            + rng.standard_normal(batch_rows)
        counts = stream.append({"signal": signal, "hr": hr})
        ran = bd.streams.tick() if tick else []
        yield {"batch": b, **counts,
               "ran": [(cq_name, resp.plan_cache_hit)
                       for cq_name, resp in ran]}


def stream_mimic_paired_waveforms(bd: BigDawg, *, batch_rows: int = 48,
                                  num_batches: int = 24,
                                  capacity: int = 8192, seed: int = 0,
                                  jitter: float = 2.0,
                                  max_delay: float = 6.0,
                                  shards: int = 2,
                                  abp_name: str = "mimic2v26.abp_stream",
                                  ecg_name: str = "mimic2v26.ecg_stream",
                                  engine_name: str = "streamstore0",
                                  tick: bool = True) -> Iterator[Dict]:
    """Jittered two-stream MIMIC waveform feed — the cross-stream
    event-time workload (paper §III: correlating ABP and ECG alarms).

    Two event-time streams, ``abp`` (arterial blood pressure) and
    ``ecg``, share one ``ts`` axis at 1 row/tick with the ECG phase-
    shifted by 0.25.  Delivery is *out of order*: each batch's rows are
    shuffled by a bounded network jitter (arrival order = order of
    ``ts + U(-jitter, jitter)``), so insertion buffers and watermarks do
    real work, while ``jitter < max_delay / 2`` guarantees no row is
    ever late — the streams reconstruct the exact in-order signal.
    Yields a per-batch dict with append counts, the rows appended in
    arrival order (``rows``: stream name -> columns, so a caller can
    rebuild any answer from the raw feed), both watermarks, and the
    standing queries that ran on that tick; after the final batch both
    streams are flushed (punctuation) and one more tick runs so standing
    joins see the last closed window.
    """
    assert jitter >= 0 and max_delay > 2 * jitter, (jitter, max_delay)
    rng = np.random.default_rng(seed)
    engine = bd.engines[engine_name]
    streams = {}
    for sname, phase in ((abp_name, 0.0), (ecg_name, 0.25)):
        if not engine.has(sname):
            field = "abp" if sname == abp_name else "ecg"
            bd.register_stream(engine_name, sname, ("ts", field),
                               capacity, shards=shards,
                               ts_field="ts", max_delay=max_delay)
        streams[sname] = engine.get(sname)

    def _emit(b: int, ran) -> Dict:
        return {"batch": b,
                "watermarks": {n: s.watermark
                               for n, s in streams.items()},
                "late": {n: s.total_late for n, s in streams.items()},
                "ran": [(cq_name, resp.plan_cache_hit)
                        for cq_name, resp in ran]}

    base = 0.0
    for b in range(num_batches):
        t = base + np.arange(batch_rows, dtype=np.float64)
        base += batch_rows
        order = np.argsort(t + rng.uniform(-jitter, jitter, batch_rows))
        abp_ts = t[order]
        abp = (90.0 + 12.0 * np.sin(2 * np.pi * t / 360.0)
               + 0.5 * rng.standard_normal(batch_rows))[order]
        counts_abp = streams[abp_name].append({"ts": abp_ts,
                                               "abp": abp})
        order = np.argsort(t + rng.uniform(-jitter, jitter, batch_rows))
        ecg_ts = (t + 0.25)[order]
        ecg = (np.sin(2 * np.pi * t / 6.0)
               + 0.1 * rng.standard_normal(batch_rows))[order]
        counts_ecg = streams[ecg_name].append({"ts": ecg_ts,
                                               "ecg": ecg})
        ran = bd.streams.tick() if tick else []
        yield {**_emit(b, ran), "appended": {
            abp_name: counts_abp["appended"],
            ecg_name: counts_ecg["appended"]},
            "rows": {abp_name: {"ts": abp_ts, "abp": abp},
                     ecg_name: {"ts": ecg_ts, "ecg": ecg}}}
    # punctuation: close the tail windows and let standing joins see them
    for s in streams.values():
        s.flush()
    ran = bd.streams.tick() if tick else []
    yield _emit(num_batches, ran)
