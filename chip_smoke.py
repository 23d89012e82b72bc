"""Run the polystore's served path once on one TPU chip and check every
answer against a plain reference.

    python chip_smoke.py

Three phases drive the system through the entry points a user calls
(``default_deployment``, ``BigDawg.query``, ``register_stream`` with a
``StreamSpec``, the ``FrontDoor``, ``register_model`` and ``bdml``):

  (a) batch polystore: ``load_mimic_demo`` at MIMIC II scale (32,000
      subjects, 10**6 ``poe_order`` rows, an 8-lead waveform of 24 h at
      125 Hz resident on ``densehbm0``), then the paper's section VI
      queries: ``bdrel``, a ``bdarray`` filter/aggregate over the
      waveform, ``bdtext``, the ``bdcast`` relational -> array cast and
      ``bdcatalog``;
  (b) standing queries through the ``FrontDoor`` on the compiled (jit)
      query path: a 4-shard stream of 64 beds x 125 Hz plus the jittered
      ABP/ECG pair, two tenants subscribed to tumbling and sliding
      windows and aggregates, event-time windows and the interval join;
  (c) ``bdml``: qwen2-1.5b at its published config scores 256-row
      windows through a standing ``infer`` subscription.

The script needs a TPU: anywhere else it exits 2 before doing any work.
Any failed phase or answer that disagrees with its reference raises, so
the exit code is non-zero and no result line is printed.  The last line
of standard output is one JSON object naming the device.

Tolerances.  Results arrive in float32 (jax's default dtype); the
references are float64 numpy over the same generated rows.

  * Gathers (window, ewindow and join rows, the cast): row counts,
    timestamps, bed ids and seq order must be exact; values may differ
    by one float32 rounding of the float64 ring value (2**-23 relative).
  * A reduction over n rows: ``|got - ref| <= (n + 1) * 2**-24 *
    sum(|x|)``, the worst-case error of float32 summation in any order
    plus the rounding of its inputs.  Counts, min and max are exact up to
    that one input rounding.
  * The 8.64e7-sample waveform average: the bound above is vacuous at
    that n, so the check is ``1e-4 * mean(|x|)``.  A blocked float32
    accumulation of 1e4-1e5 terms per partial sum drifts by about
    sqrt(m) * 2**-24 ~ 2e-5 relative, a fifth of the limit, while losing
    one of the 8 leads moves the answer by ~1e-1.
  * ``bdml`` scores against a jitted ``registry.forward`` on tokens the
    script quantizes itself, with the NLL taken in float64 numpy from
    the logits: ``1e-5`` relative.  Both forwards are the same XLA
    program, so the logits agree bitwise; what is left is the island's
    float32 log-softmax over the 151,936-entry vocabulary and its mean
    over 255 positions, whose tree reductions drift by about
    (log2(151936) + log2(255)) * 2**-24 ~ 1.5e-6 relative.  An eager
    forward is no reference on the chip: at the default matmul
    precision (bfloat16 passes) eager and jitted programs round
    differently and their NLLs differ by ~3e-3 relative.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

U32 = 2.0 ** -24                     # unit roundoff of float32
MAX_DELAY = 6.0                      # the paired feed's out-of-order bound
JITTER = 2.0


class SmokeFailure(Exception):
    """An answer disagreed with its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def query_backend(name: str):
    from repro.stream import compile as qc
    before = os.environ.get(qc.BACKEND_ENV)
    os.environ[qc.BACKEND_ENV] = name
    try:
        yield
    finally:
        if before is None:
            os.environ.pop(qc.BACKEND_ENV, None)
        else:
            os.environ[qc.BACKEND_ENV] = before


# -- comparison helpers ------------------------------------------------------
def expect_rows(what, got, ref, exact=()):
    """A gathered view: same columns in the same order and shape, exact
    on ``exact`` columns, one float32 rounding elsewhere."""
    check(list(got) == list(ref),
          f"{what}: columns {list(got)} != {list(ref)}")
    for k, r in ref.items():
        g = np.asarray(got[k], np.float64)
        r = np.asarray(r, np.float64)
        check(g.shape == r.shape, f"{what} [{k}]: shape {g.shape} != "
                                  f"{r.shape}")
        if k in exact:
            check(np.array_equal(g, r), f"{what} [{k}]: not exact")
        else:
            err = np.abs(g - r)
            check(bool(np.all(err <= 2 * U32 * np.abs(r))),
                  f"{what} [{k}]: max err {err.max(initial=0.0):.3e}")


def expect_reduction(what, got, fn, terms):
    """One aggregate value ``fn(terms)`` within the float32 bound."""
    terms = np.asarray(terms, np.float64).reshape(-1)
    g = float(np.asarray(got).reshape(-1)[0])
    n = terms.shape[0]
    if fn == "count":
        check(g == n, f"{what}: count {g} != {n}")
        return
    ref = {"sum": terms.sum, "avg": terms.mean,
           "max": terms.max, "min": terms.min}[fn]()
    if fn in ("max", "min"):
        tol = 2 * U32 * abs(ref)
    else:
        tol = (n + 1) * U32 * float(np.abs(terms).sum())
        tol = tol / n if fn == "avg" else tol
    check(abs(g - ref) <= tol,
          f"{what}: {g!r} vs {ref!r} (|err| {abs(g - ref):.3e} > "
          f"{tol:.3e})")


# -- phase (a): batch polystore ----------------------------------------------
def phase_batch(*, num_patients: int, num_orders: int, wave_len: int,
                num_logs: int, seed: int = 0) -> dict:
    import jax
    from repro.core.api import default_deployment
    from repro.data.mimic import load_mimic_demo

    bd = default_deployment()
    load_mimic_demo(bd, num_patients=num_patients, num_orders=num_orders,
                    wave_len=wave_len, num_logs=num_logs, seed=seed)
    out = {}

    # the waveform lives on the device as one array (HBM on a TPU)
    sig = bd.engines["densehbm0"].get("mimic2v26.waveform").attrs["signal"]
    platform = jax.devices()[0].platform
    check({d.platform for d in sig.devices()} == {platform},
          f"waveform not on the {platform} device: {sig.devices()}")
    out["waveform_bytes"] = int(sig.nbytes)
    sig_np = np.asarray(sig, np.float64)

    # relational island
    orders = bd.engines["hoststore0"].get("mimic2v26.poe_order").columns
    dose = np.asarray(orders["dose"], np.float64)
    r = bd.query("bdrel(select count(*) from mimic2v26.poe_order"
                 " where dose > 25.0)").value
    expect_reduction("bdrel count(dose > 25)",
                     r.columns["count_poe_id"], "count",
                     dose[dose > 25.0])
    pts = bd.engines["hoststore0"].get("mimic2v26.d_patients").columns
    sex = np.asarray(pts["sex"])
    dob = np.asarray(pts["dob_year"], np.float64)
    r = bd.query("bdrel(select sex, avg(dob_year) from"
                 " mimic2v26.d_patients group by sex)").value
    check(np.array_equal(np.asarray(r.columns["sex"]), np.unique(sex)),
          "bdrel group by: keys")
    for i, s in enumerate(np.unique(sex)):
        expect_reduction(f"bdrel avg(dob_year) sex={s}",
                         r.columns["avg_dob_year"][i:i + 1], "avg",
                         dob[sex == s])

    # array island: filter + aggregate over the whole waveform
    mask = sig_np > 1.0
    base = "bdarray(aggregate(filter(mimic2v26.waveform, signal>1.0), {})"
    r = bd.query(base.format("count(signal)") + ")").value
    check(int(np.asarray(r.attrs["count_signal"])[0]) == int(mask.sum()),
          "bdarray count(signal > 1)")
    r = bd.query(base.format("max(signal)") + ")").value
    expect_reduction("bdarray max(signal > 1)", r.attrs["max_signal"],
                     "max", sig_np[mask])
    r = bd.query(base.format("avg(signal)") + ")").value
    got = float(np.asarray(r.attrs["avg_signal"])[0])
    ref = float(sig_np[mask].mean())
    tol = 1e-4 * float(np.abs(sig_np[mask]).mean())
    check(abs(got - ref) <= tol,
          f"bdarray avg(signal > 1): {got!r} vs {ref!r}")
    out["filtered_samples"] = int(mask.sum())

    # text island: key-range scan
    kv = bd.engines["kvstore0"].get("mimic_logs")
    r = bd.query("bdtext({ 'op' : 'range', 'table' : 'mimic_logs',"
                 " 'range' : { 'start' : ['r_0001','',''],"
                 " 'end' : ['r_0015','',''] } })").value
    want = [(k, v) for k, v in zip(kv.keys, kv.values)
            if "r_0001" <= k[0] <= "r_0015"]
    check(r == want and len(want) == min(15, num_logs - 1),
          f"bdtext range: {len(r)} rows, want {len(want)}")

    # the relational -> array cast of the whole poe_order table
    r = bd.query("bdarray(scan(bdcast(bdrel(select poe_id, subject_id from"
                 " mimic2v26.poe_order), poe_order_copy,"
                 " '<subject_id:int32>[poe_id=0:*,10000000,0]', array)))")
    order = np.argsort(np.asarray(orders["poe_id"]), kind="stable")
    check(r.value.dim_names == ("poe_id",), "bdcast: dims")
    expect_rows("bdcast rel->array", r.value.attrs,
                {"subject_id": np.asarray(orders["subject_id"])[order]},
                exact=("subject_id",))
    out["cast_rows"] = int(order.shape[0])

    # catalog
    r = bd.query("bdcatalog(select name, connection_properties"
                 " from engines)").value
    check({(row["name"], row["connection_properties"]) for row in r}
          == {(e.name, e.kind) for e in bd.engines.values()},
          "bdcatalog engines")
    return out


# -- phase (b): standing queries through the FrontDoor ------------------------
def _flushed(batches, field, final):
    """The rows an event-time stream has flushed: everything at or below
    its watermark (max ts seen - max delay; max ts after punctuation),
    in ts order."""
    ts = np.concatenate([b["ts"] for b in batches])
    val = np.concatenate([b[field] for b in batches])
    wm = ts.max() if final else ts.max() - MAX_DELAY
    keep = ts <= wm
    order = np.argsort(ts[keep], kind="stable")
    return wm, {"ts": ts[keep][order], field: val[keep][order]}


def _ewindow(wm, rows, span):
    start = math.floor((wm - span) / span) * span
    sel = (rows["ts"] >= start) & (rows["ts"] < start + span)
    return {f: v[sel] for f, v in rows.items()}


def _tumbling(rows, size):
    n = next(iter(rows.values())).shape[0]
    k = n // size - 1
    return {f: v[k * size:(k + 1) * size] for f, v in rows.items()}


def _sliding(rows, size, slide):
    n = next(iter(rows.values())).shape[0]
    starts = range(0, n - size + 1, slide)
    return {f: np.stack([v[s:s + size] for s in starts])
            for f, v in rows.items()}


def _join(left, right, tol):
    order = np.argsort(right["ts"], kind="stable")
    rs = {f: v[order] for f, v in right.items()}
    li, ri = [], []
    for i, t in enumerate(left["ts"]):
        hit = np.nonzero((rs["ts"] >= t - tol) & (rs["ts"] <= t + tol))[0]
        li.extend([i] * hit.shape[0])
        ri.extend(hit.tolist())
    out = {f"l_{f}": v[li] for f, v in left.items()}
    out.update({f"r_{f}": v[ri] for f, v in rs.items()})
    out["dt"] = rs["ts"][ri] - left["ts"][li]
    return out


def phase_standing(*, beds: int, hz: int, ticks: int, shards: int,
                   seed: int = 0) -> dict:
    from repro.core.api import default_deployment
    from repro.data.mimic import stream_mimic_paired_waveforms
    from repro.serve.frontdoor import FrontDoor
    from repro.stream import compile as qc
    from repro.stream.spec import Sharding, StreamSpec

    rows_per_tick = beds * hz
    w, half = hz // 2, hz // 4
    bd = default_deployment()
    bd.register_stream("streamstore0", StreamSpec(
        "icu.beds", ("t", "bed", "abp"),
        capacity=rows_per_tick * (ticks + 4),
        sharding=Sharding(shards=shards, shard_key="bed")))
    door = FrontDoor(bd, stream_engine="streamstore0")
    abp, ecg = "mimic2v26.abp_stream", "mimic2v26.ecg_stream"
    r = rows_per_tick
    queries = {
        "beds_avg": f"aggregate(window(icu.beds, {r}), avg(abp))",
        "beds_max": f"aggregate(window(icu.beds, {r}), max(abp))",
        "beds_window": f"window(icu.beds, {r})",
        "beds_slide_max": f"aggregate(window(icu.beds, {r}, {r // 2}),"
                          f" max(abp))",
        "abp_window": f"window({abp}, {w})",
        "abp_slide_max": f"aggregate(window({abp}, {w}, {half}), max(abp))",
        "ecg_slide": f"window({ecg}, {w}, {half})",
        "ecg_ewindow": f"ewindow({ecg}, {w})",
        "abp_ewindow_avg": f"aggregate(ewindow({abp}, {w}), avg(abp))",
        "join": f"join(ewindow({abp}, {w}), ewindow({ecg}, {w}),"
                f" on=ts, tol=0.5)",
    }
    tenants = {"ward": ["beds_avg", "beds_max", "beds_window",
                        "beds_slide_max", "abp_window", "abp_slide_max"],
               "cardio": ["beds_avg", "ecg_slide", "ecg_ewindow",
                          "abp_ewindow_avg", "join"]}
    subs = []
    for tenant, names in tenants.items():
        session = door.open_session(tenant)
        for name in names:
            subs.append((name, session.subscribe(
                f"bdstream({queries[name]})")))

    rng = np.random.default_rng(seed)
    bed_rows = []
    pair_rows = {abp: [], ecg: []}
    beds_stream = bd.engines["streamstore0"].get("icu.beds")
    feed = stream_mimic_paired_waveforms(
        bd, batch_rows=hz, num_batches=ticks, capacity=hz * 64, seed=seed,
        jitter=JITTER, max_delay=MAX_DELAY, shards=1)
    delivered = 0
    qc.reset_stats()
    with query_backend("jit"):
        for tick in range(ticks + 1):
            final = tick == ticks
            if not final:
                t = tick * hz + np.repeat(np.arange(hz), beds)
                bed = np.tile(np.arange(beds), hz).astype(np.float64)
                wave = (90.0 + 0.1 * bed
                        + 12.0 * np.sin(2 * np.pi * 1.2 * t / hz)
                        + 0.5 * rng.standard_normal(rows_per_tick))
                batch = {"t": t.astype(np.float64), "bed": bed, "abp": wave}
                beds_stream.append(batch)
                bed_rows.append(batch)
            item = next(feed)                # appends the pair, ticks
            for name, rows in item.get("rows", {}).items():
                pair_rows[name].append(rows)
            tick_no = bd.streams.ticks
            beds_all = {f: np.concatenate([b[f] for b in bed_rows])
                        for f in ("t", "bed", "abp")}
            wm_a, abp_rows = _flushed(pair_rows[abp], "abp", final)
            wm_e, ecg_rows = _flushed(pair_rows[ecg], "ecg", final)
            refs = {
                "beds_window": _tumbling(beds_all, r),
                "abp_window": _tumbling(abp_rows, w),
                "ecg_slide": _sliding(ecg_rows, w, half),
                "ecg_ewindow": _ewindow(wm_e, ecg_rows, w),
                "join": _join(_ewindow(wm_a, abp_rows, w),
                              _ewindow(wm_e, ecg_rows, w), 0.5),
            }
            terms = {
                "beds_avg": ("avg", _tumbling(beds_all, r)["abp"]),
                "beds_max": ("max", _tumbling(beds_all, r)["abp"]),
                "beds_slide_max": ("max",
                                   _sliding(beds_all, r, r // 2)["abp"]),
                "abp_slide_max": ("max", _sliding(abp_rows, w, half)["abp"]),
                "abp_ewindow_avg": ("avg",
                                    _ewindow(wm_a, abp_rows, w)["abp"]),
            }
            for name, sub in subs:
                got = sub.poll()
                check(len(got) == 1 and got[0][0] == tick_no,
                      f"{name}: deliveries {[g[0] for g in got]} on tick "
                      f"{tick_no}")
                value = got[0][1]
                what = f"tick {tick_no} {name}"
                if name in refs:
                    cols = getattr(value, "columns", None) or value.attrs
                    exact = {"t", "bed", "ts", "l_ts", "r_ts", "dt"}
                    expect_rows(what, cols, refs[name],
                                exact=exact & set(refs[name]))
                else:
                    fn, vals = terms[name]
                    expect_reduction(what, next(iter(value.attrs.values())),
                                     fn, vals)
                delivered += 1
    errors = {cq.name: cq.last_error for cq in bd.streams.queries.values()
              if cq.errors}
    check(not errors, f"standing query errors: {errors}")
    check(bd.streams.listener_errors == 0,
          f"listener errors: {bd.streams.last_listener_error}")
    stats = qc.stats()
    check(stats["fallbacks"] == 0 and stats["executions"] > 0,
          f"compiled path: {stats}")
    shared = door.stats()["shared_queries"]
    door.close()
    return {"ticks": ticks + 1, "deliveries": delivered,
            "bed_rows": int(sum(b["t"].shape[0] for b in bed_rows)),
            "shared_queries": shared,
            "compile": {k: stats[k] for k in
                        ("compiles", "cache_hits", "executions",
                         "interpreted", "fallbacks")}}


# -- phase (c): bdml at published widths --------------------------------------
def phase_bdml(*, arch: str, rows: int, ticks: int, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core.api import default_deployment
    from repro.models import registry
    from repro.serve.engine import ServeConfig
    from repro.serve.frontdoor import FrontDoor
    from repro.stream import ml
    from repro.stream.spec import StreamSpec

    bd = default_deployment()
    handle = bd.register_model("scorer", arch=arch, seed=seed)
    cfg = registry.get_config(handle.arch, reduced=handle.reduced)
    forward = jax.jit(lambda p, t: registry.forward(
        p, {"tokens": t}, cfg, None)[0])
    door = FrontDoor(bd, ServeConfig(streams=(StreamSpec(
        "icu.bed0_abp", ("ts", "abp"), capacity=rows * 8),)),
        stream_engine="streamstore0")
    q = (f"bdml(infer(window(icu.bed0_abp, {rows}), models.scorer,"
         f" field=abp))")
    subs = [door.open_session(t).subscribe(q) for t in ("ward", "cardio")]
    stream = bd.engines["streamstore0"].get("icu.bed0_abp")
    rng = np.random.default_rng(seed)
    scored0 = ml.stats()["windows_scored"]
    scores, worst = [], 0.0
    with query_backend("jit"):
        for tick in range(ticks):
            t = tick * rows + np.arange(rows, dtype=np.float64)
            abp = (90.0 + 12.0 * np.sin(2 * np.pi * 1.2 * t / 125.0)
                   + 0.5 * rng.standard_normal(rows))
            stream.append({"ts": t, "abp": abp})
            bd.streams.tick()
            loaded = ml.load_model(handle.arch, handle.seed, handle.reduced)
            # windows are served in float32, the ambient dtype (ROADMAP
            # D2), so the island tokenizes float32-rounded rows: min/max
            # binning into the vocabulary
            v = abp.astype(np.float32).astype(np.float64)
            toks = np.minimum(np.floor((v - v.min()) / (v.max() - v.min())
                                       * (cfg.vocab_size - 1)),
                              cfg.vocab_size - 1).astype(np.int32)
            logits = np.asarray(forward(loaded.params,
                                        jnp.asarray(toks[None])),
                                np.float64)[0, :-1]
            top = logits.max(-1)
            lse = top + np.log(np.exp(logits - top[:, None]).sum(-1))
            ref = float(np.mean(lse - logits[np.arange(rows - 1),
                                             toks[1:]]))
            for sub in subs:
                got = sub.poll()
                check(len(got) == 1, f"bdml: {len(got)} deliveries")
                cols = got[0][1].columns
                check(int(np.asarray(cols["rows"])[0]) == rows
                      and int(np.asarray(cols["window"])[0]) == 0,
                      "bdml: window/rows")
                score = float(np.asarray(cols["score"])[0])
                check(math.isfinite(score), f"bdml: score {score}")
                check(abs(score - ref) <= 1e-5 * abs(ref),
                      f"bdml tick {tick}: {score!r} vs direct forward "
                      f"{ref!r}")
                worst = max(worst, abs(score - ref) / abs(ref))
            scores.append(score)
    cq_errors = [cq.last_error for cq in bd.streams.queries.values()
                 if cq.errors]
    check(not cq_errors, f"bdml standing query errors: {cq_errors}")
    check(bd.streams.listener_errors == 0,
          f"listener errors: {bd.streams.last_listener_error}")
    scored = ml.stats()["windows_scored"] - scored0
    check(scored > 0, "bdml scored no windows")
    published = (handle.reduced is False
                 and loaded.cfg == registry.get_config(arch))
    door.close()
    n_params = sum(int(x.size) for x in jax.tree.leaves(loaded.params))
    return {"arch": handle.arch, "published_config": published,
            "layers": cfg.num_layers, "d_model": cfg.d_model,
            "vocab": cfg.vocab_size, "params": n_params,
            "windows_scored": scored, "scores": scores,
            "max_rel_err": worst}


# -- the run -------------------------------------------------------------------
CHIP_SIZES = {
    "batch": dict(num_patients=32_000, num_orders=1_000_000,
                  wave_len=10_800_000, num_logs=100_000),
    "standing": dict(beds=64, hz=125, ticks=12, shards=4),
    "bdml": dict(arch="qwen2-1.5b", rows=256, ticks=3),
}
PHASES = (("batch", phase_batch), ("standing", phase_standing),
          ("bdml", phase_bdml))


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    from repro.stream import compile as qc
    from repro.stream import ml

    cache_dir = qc.use_compile_cache()
    compile_s = collections.Counter()
    events = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compile_s.update(
            {name: secs} if name.startswith("/jax/core/compile/") else {}))
    jax.monitoring.register_event_listener(
        lambda name, **_: events.update([name]))
    print(f"platform: {dev.platform}")
    print(f"device_kind: {dev.device_kind}")
    print(f"device_count: {len(devices)}")
    print(f"compile_cache: {cache_dir}")
    print("cuts: none (every phase runs at the sizes below)")
    for name, fn in PHASES:
        print(f"[{name}] sizes: {json.dumps(CHIP_SIZES[name])}", flush=True)
        c0, h0 = sum(compile_s.values()), dict(events)
        t0 = time.perf_counter()
        out = fn(**CHIP_SIZES[name])
        wall = time.perf_counter() - t0
        gc.collect()                         # free the phase's device data
        comp = sum(compile_s.values()) - c0
        hits = (events["/jax/compilation_cache/cache_hits"]
                - h0.get("/jax/compilation_cache/cache_hits", 0))
        print(f"[{name}] wall_s: {wall} compile_s: {comp} "
              f"persistent_cache_hits: {hits}")
        print(f"[{name}] result: {json.dumps(out)}", flush=True)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(f"compile.stats: {json.dumps(qc.stats())}")
    print(f"ml.stats: {json.dumps(ml.stats())}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
