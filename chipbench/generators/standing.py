"""Standing queries of an ICU ward through the front door, as an open
loop at the ward's real rate.

Every ``batch_period_s`` one batch arrives: ``period x hz`` samples of
every bed on the bed stream, and the same span of one patient's ABP/ECG
pair, out of order within the batch.  The harness appends the batch,
ticks the stream runtime, and every tenant polls its subscriptions and
brings each result to the host.  A delivery's latency runs from the
batch's scheduled arrival to that moment, so a tick that overruns the
period delays every later batch and the tail shows it.  Event-time
windows hold rows for the watermark (``max_delay``) by design; that
delay is the query's, not the system's, and is not in the latency.

The queries are data: each entry of the traffic's ``queries`` names a
stream, a window (tumbling, sliding or event-time) and an optional
aggregate, or an interval join; the generator writes the BQL from it and
the reference evaluates the same entry.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import answers, data
from chipbench.harness import annotate
from chipbench.reference import stream as ref

EXACT = ("t", "bed", "ts", "l_ts", "r_ts", "dt")


@dataclasses.dataclass
class State:
    cfg: Dict
    traffic: Dict
    seed: int
    bd: Any
    door: Any
    subs: List[Tuple[Dict, Any]]
    streams: Dict[str, Any]
    rng: np.random.Generator
    beds: List[Dict[str, np.ndarray]]
    abp: List[Dict[str, np.ndarray]]
    ecg: List[Dict[str, np.ndarray]]


def sizes(cfg: Dict, traffic: Dict) -> Dict[str, Any]:
    hz = cfg["hz"]
    beds = int(traffic["beds"])
    pair = cfg["pair"]
    return {
        "beds": beds, "hz": hz,
        "spb": int(round(hz * cfg["batch_period_s"])),
        "bed_capacity": int(cfg["bed_stream"]["ring_seconds"] * hz * beds),
        "pair_capacity": int(pair["ring_seconds"] * hz),
        "max_delay": pair["max_delay_s"] * hz,
        "jitter": pair["jitter_s"] * hz,
    }


def rows_of(spec: Dict, stream: str, beds: int, key: str) -> int:
    """A window size or slide in rows: the bed stream carries every bed,
    so its samples count ``beds`` rows each."""
    n = spec[key] * (beds if stream == "beds" else 1)
    if n != int(n):
        raise ValueError(f"{spec['name']}: {key} is not a whole number "
                         f"of rows at {beds} beds")
    return int(n)


def bql(spec: Dict, names: Dict[str, str], beds: int) -> str:
    def win(stream: str) -> str:
        s = names[stream]
        if spec["window"] == "ewindow":
            return f"ewindow({s}, {spec['size']})"
        size = rows_of(spec, stream, beds, "size")
        if spec["window"] == "sliding":
            return (f"window({s}, {size}, "
                    f"{rows_of(spec, stream, beds, 'slide')})")
        return f"window({s}, {size})"

    if "join" in spec:
        a, b = spec["join"]
        body = f"join({win(a)}, {win(b)}, on=ts, tol={spec['tol']})"
    elif spec.get("agg"):
        body = f"aggregate({win(spec['stream'])}, " \
               f"{spec['agg']}({spec['field']}))"
    else:
        body = win(spec["stream"])
    return f"bdstream({body})"


def setup(cfg, traffic, seed, devices, log) -> State:
    from repro.core.api import default_deployment
    from repro.serve.frontdoor import FrontDoor
    from repro.stream.spec import EventTime, Sharding, StreamSpec

    z = sizes(cfg, traffic)
    bs, pair = cfg["bed_stream"], cfg["pair"]
    bd = default_deployment()
    bd.register_stream("streamstore0", StreamSpec(
        bs["name"], ("t", "bed", "abp"), capacity=z["bed_capacity"],
        sharding=Sharding(shards=bs["shards"], shard_key=bs["shard_key"])))
    for key, field in (("abp", "abp"), ("ecg", "ecg")):
        bd.register_stream("streamstore0", StreamSpec(
            pair[key], ("ts", field), capacity=z["pair_capacity"],
            event_time=EventTime("ts", max_delay=z["max_delay"])))
    door = FrontDoor(bd, stream_engine="streamstore0",
                     **cfg["front_door"])
    names = {"beds": bs["name"], "abp": pair["abp"], "ecg": pair["ecg"]}
    engine = bd.engines["streamstore0"]
    sessions = {}
    subs = []
    for spec in traffic["queries"]:
        q = bql(spec, names, z["beds"])
        for tenant in spec["tenants"]:
            if tenant not in sessions:
                sessions[tenant] = door.open_session(tenant)
            subs.append((spec, sessions[tenant].subscribe(q)))
    log(f"subscriptions: {len(subs)} over {len(door._shared)} shared "
        f"queries, {z['beds']} beds")
    st = State(cfg, traffic, seed, bd, door, subs,
               {k: engine.get(v) for k, v in names.items()},
               np.random.default_rng(data.sub_seed(seed, "feed")),
               [], [], [])
    # warm-up: fill every ring and pass the watermark, so each query has
    # its steady shapes and delivers on every tick from here on
    fill = max(z["bed_capacity"] / (z["beds"] * z["spb"]),
               (z["pair_capacity"] + z["max_delay"]) / z["spb"])
    for _ in range(int(np.ceil(fill)) + 2):
        append(st)
        st.bd.streams.tick()
        for _, sub in subs:
            for _, value in sub.poll():
                answers.to_host(value)
    return st


def append(st: State) -> None:
    z = sizes(st.cfg, st.traffic)
    k = len(st.beds)
    beds = data.ward_batch(st.rng, k, z["beds"], z["spb"], z["hz"])
    abp, ecg = data.pair_batch(st.rng, k, z["spb"], z["jitter"],
                               st.cfg["pair"]["ecg_offset"])
    st.streams["beds"].append(beds)
    st.streams["abp"].append(abp)
    st.streams["ecg"].append(ecg)
    st.beds.append(beds)
    st.abp.append(abp)
    st.ecg.append(ecg)


def run(st: State, seconds: float, probe, log) -> Dict[str, Any]:
    period = st.cfg["batch_period_s"]
    n = max(1, int(round(seconds / period)))
    check_rng = np.random.default_rng(data.sub_seed(st.seed, "check"))
    sample = set(check_rng.choice(n, min(st.traffic["check_ticks"], n),
                                  replace=False).tolist()) | {n - 1}
    first = len(st.beds)
    lat: List[float] = []
    late: List[float] = []
    kept: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
    failed = 0
    done = {"ticks": 0, "deliveries": 0}
    probe.begin(lambda: dict(done))
    t0 = time.perf_counter()
    for i in range(n):
        due = t0 + i * period
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late.append(time.perf_counter() - due)
        with annotate("append"):
            append(st)
        with annotate("tick"):
            st.bd.streams.tick()
        tick_no = st.bd.streams.ticks
        with annotate("poll"):
            for j, (_, sub) in enumerate(st.subs):
                got = sub.poll()
                if len(got) != 1 or got[0][0] != tick_no:
                    failed += 1
                    continue
                host = answers.to_host(got[0][1])
                lat.append(time.perf_counter() - due)
                if i in sample:
                    kept[(first + i, j)] = host
        done["ticks"] += 1
        done["deliveries"] = len(lat)
        probe.step()
    log(f"deliveries: {len(lat)} of {n * len(st.subs)} due, over {n} "
        f"ticks")
    log(f"generator_late_ms: p50 {1e3 * float(np.median(late))} max "
        f"{1e3 * max(late)}")
    return {"e2e": {"event_to_result_p95_ms": 1e3 * answers.p95(lat)}
            if lat else {},
            "attempted": n * len(st.subs), "failed": failed, "kept": kept,
            "late": late}


def release(st: State) -> None:
    import gc
    st.door.close()
    st.bd = st.door = st.streams = None
    gc.collect()


def reference(st: State, spec: Dict, upto: int) -> Dict[str, np.ndarray]:
    """The answer of ``spec`` on the tick that consumed batch ``upto``."""
    z = sizes(st.cfg, st.traffic)
    md = z["max_delay"]

    def rows(stream: str):
        if stream == "beds":
            # the bed stream keeps no event time: rows in arrival order
            keep = int(np.ceil(z["bed_capacity"] / (z["beds"] * z["spb"])))
            lo = max(0, upto + 1 - keep - 1)
            part = st.beds[lo:upto + 1]
            total = (upto + 1) * z["beds"] * z["spb"]
            cols = {f: np.concatenate([b[f] for b in part])
                    for f in ("t", "bed", "abp")}
            return None, cols, total, z["bed_capacity"]
        batches = (st.abp if stream == "abp" else st.ecg)[:upto + 1]
        wm, cols = ref.flushed(batches, stream, md)
        return wm, cols, cols["ts"].shape[0], z["pair_capacity"]

    def window(stream: str):
        wm, cols, total, cap = rows(stream)
        if spec["window"] == "ewindow":
            return ref.ewindow(wm, cols, spec["size"])
        if spec["window"] == "sliding":
            return ref.sliding(ref.ring(cols, cap),
                               rows_of(spec, stream, z["beds"], "size"),
                               rows_of(spec, stream, z["beds"], "slide"))
        size = rows_of(spec, stream, z["beds"], "size")
        n = next(iter(cols.values())).shape[0]
        s = (total // size - 1) * size - (total - n)
        return {f: v[s:s + size] for f, v in cols.items()}

    if "join" in spec:
        a, b = spec["join"]
        return ref.join(window(a), window(b), spec["tol"])
    out = window(spec["stream"])
    if spec.get("agg"):
        value = ref.AGGS[spec["agg"]](out[spec["field"]].reshape(-1))
        return {f"{spec['agg']}_{spec['field']}": np.asarray([value])}
    return out


def _compare(st: State, res: Dict, control: bool) -> Dict[str, float]:
    mismatch, worst = 0, 0.0
    for (upto, j), got in sorted(res["kept"].items()):
        spec = st.subs[j][0]
        want = reference(st, spec, upto)
        if control:
            got = {k: answers.bf16(v) for k, v in want.items()}
        m, e = ref.compare(got, want, EXACT)
        mismatch += m
        worst = max(worst, e)
    return {"missing": float(res["failed"]), "mismatch": float(mismatch),
            "value_err": worst}


def check(st: State, res: Dict, log) -> Dict[str, float]:
    release(st)
    log(f"compared deliveries: {len(res['kept'])}")
    return _compare(st, res, control=False)


def control(st: State, res: Dict, log) -> Dict[str, float]:
    """The reference rounded to bfloat16 in the program's place."""
    return _compare(st, res, control=True)
