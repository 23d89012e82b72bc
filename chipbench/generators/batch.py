"""The paper's batch MIMIC II polystore under one analyst, as a closed
loop over a fixed set of queries.

The traffic lists the query family and its parameters; every seed
replays the same queries in its own random order, one after another,
each timed from submission to its result on the host.  Two families:

* ``cast``: a cohort of ``poe_order`` by dose, cast from the relational
  island into the array island
  (``bdarray(scan(bdcast(bdrel(select ... where dose > d), ...))``);
* ``array``: a filter and aggregate over the waveform resident on the
  array engine (``bdarray(aggregate(filter(W, signal > x), f(signal)))``).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import answers, data
from chipbench.harness import annotate

CAST = ("bdarray(scan(bdcast(bdrel(select poe_id, subject_id from"
        " mimic2v26.poe_order where dose > {d}), cohort{i},"
        " '<subject_id:int32>[poe_id=0:*,10000000,0]', array)))")
ARRAY = ("bdarray(aggregate(filter(mimic2v26.waveform, signal > {x}),"
         " {f}(signal)))")


@dataclasses.dataclass
class State:
    cfg: Dict
    traffic: Dict
    seed: int
    bd: Any
    arrays: Dict[str, Any]
    queries: List[str]
    params: List[Dict]
    rows: Dict[str, np.ndarray] = None


def queries(traffic: Dict):
    """(BQL, parameters) of every query of the mix."""
    out = []
    for i, p in enumerate(traffic["queries"]):
        if traffic["family"] == "cast":
            out.append((CAST.format(d=p["dose_above"], i=i), p))
        else:
            out.append((ARRAY.format(x=p["signal_above"], f=p["agg"]), p))
    return out


def setup(cfg, traffic, seed, devices, log) -> State:
    import jax.numpy as jnp
    from repro.core import datamodel as dm
    from repro.core.api import default_deployment

    z = cfg
    arrays = data.polystore_arrays(
        seed, num_patients=z["patients"], num_orders=z["orders"],
        leads=z["leads"], bed_days=z["bed_days"],
        samples_per_day=z["samples_per_day"],
        amplitudes=z["lead_amplitudes"])
    bd = default_deployment()
    patients = dm.Table({
        "subject_id": jnp.arange(z["patients"], dtype=jnp.int32),
        "sex": arrays["sex"], "dob_year": arrays["dob_year"],
        "hospital_expire_flg": arrays["expire"]})
    bd.register_object("hoststore0", "mimic2v26.d_patients", patients,
                       fields=tuple(patients.fields))
    orders = dm.Table({
        "poe_id": jnp.arange(z["orders"], dtype=jnp.int32),
        "subject_id": arrays["subject_id"],
        "icustay_id": arrays["icustay_id"], "dose": arrays["dose"]})
    for engine in ("hoststore0", "hoststore1"):
        bd.register_object(engine, "mimic2v26.poe_order", orders,
                           fields=tuple(orders.fields))
    bd.register_object("densehbm0", "mimic2v26.waveform", dm.ArrayObject(
        attrs={"signal": arrays["signal"]}, dim_names=("lead", "tick")),
        fields=("signal",))
    keys, values = data.notes(seed, z["notes"])
    bd.register_object("kvstore0", "mimic_logs", dm.KVTable(keys, values),
                       fields=("row", "colfam", "colqual", "value"))
    qs = queries(traffic)
    st = State(cfg, traffic, seed, bd, arrays, [q for q, _ in qs],
               [p for _, p in qs])
    for _ in range(int(traffic["warm_rounds"])):
        for q in st.queries:
            answers.to_host(bd.query(q).value)
    return st


def run(st: State, seconds: float, probe, log) -> Dict[str, Any]:
    rng = np.random.default_rng(data.sub_seed(st.seed, "order"))
    keep = answers.Reservoir(
        np.random.default_rng(data.sub_seed(st.seed, "check")),
        int(st.traffic["check_answers"]))
    lat: List[float] = []
    failed = 0
    done = {"queries": 0}
    order: List[int] = []
    probe.begin(lambda: dict(done))
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if not order:
            order = rng.permutation(len(st.queries)).tolist()
        i = order.pop()
        q0 = time.perf_counter()
        try:
            with annotate("query"):
                host = answers.to_host(st.bd.query(st.queries[i]).value)
        except Exception as exc:                     # noqa: BLE001
            failed += 1
            log(f"query {i} failed: {type(exc).__name__}: {exc}")
            continue
        lat.append(time.perf_counter() - q0)
        with annotate("keep"):
            keep.offer((i, host))
        done["queries"] += 1
        probe.step()
    log(f"queries: {len(lat)} answered, {failed} failed")
    return {"e2e": {"query_p95_ms": 1e3 * answers.p95(lat)} if lat else {},
            "attempted": len(lat) + failed, "failed": failed,
            "kept": [item for _, item in keep.items]}


def release(st: State) -> Dict[str, np.ndarray]:
    """Free the deployment; the data the harness made and the mix reads
    stays, on the host."""
    need = ("subject_id", "dose") if st.traffic["family"] == "cast" \
        else ("signal",)
    host = {k: np.asarray(st.arrays[k]) for k in need}
    st.bd = st.arrays = None
    gc.collect()
    return host


def cohort(p: Dict, rows: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The reference answer of one cast query."""
    sel = rows["dose"] > np.float32(p["dose_above"])
    return {"subject_id": rows["subject_id"][sel]}


def wave_answers(sig: np.ndarray, params: List[Dict], control: bool,
                 workers: int = 8) -> Dict[float, Dict[str, float]]:
    """The reference answers of the array queries ``params`` over the
    waveform ``sig`` (float32, as the program holds it; sums in float64),
    keyed by threshold: count, average, maximum and the mean magnitude
    of the samples above it.  With ``control`` the samples are first
    rounded to bfloat16.  A few rows at a time, on ``workers`` threads,
    so a waveform of several GB needs no copy of its size."""
    from concurrent.futures import ThreadPoolExecutor

    xs = sorted({float(p["signal_above"]) for p in params})
    step = max(1, sig.shape[0] // (4 * workers))

    def block(lo: int):
        rows = sig[lo:lo + step]
        if control:
            rows = answers.bf16(rows).astype(np.float32)
        out = []
        for x in xs:
            v = rows[rows > np.float32(x)]
            out.append((v.size, v.sum(dtype=np.float64),
                        float(v.max()) if v.size else -np.inf,
                        np.abs(v).sum(dtype=np.float64)))
        return out

    with ThreadPoolExecutor(workers) as pool:
        parts = list(pool.map(block, range(0, sig.shape[0], step)))
    res = {}
    for k, x in enumerate(xs):
        n = sum(part[k][0] for part in parts)
        total = sum(part[k][1] for part in parts)
        res[x] = {"count": float(n), "avg": total / max(n, 1),
                  "max": max(part[k][2] for part in parts),
                  "scale": sum(part[k][3] for part in parts) / max(n, 1)}
    return res


def _compare(st: State, res: Dict, control: bool) -> Dict[str, float]:
    fam = st.traffic["family"]
    mismatch, worst = 0, 0.0
    if fam == "array":
        params = [st.params[i] for i, _ in res["kept"]]
        want_all = wave_answers(st.rows["signal"], params, False)
        low_all = wave_answers(st.rows["signal"], params, True) \
            if control else None
    else:
        low = {"subject_id": st.rows["subject_id"],
               "dose": answers.bf16(st.rows["dose"]).astype(np.float32)} \
            if control else None
    for i, got in res["kept"]:
        p = st.params[i]
        if fam == "cast":
            w = cohort(p, st.rows)["subject_id"]
            g = cohort(p, low)["subject_id"] if control \
                else got.get("subject_id")
            mismatch += int(g is None or g.shape != w.shape
                            or not np.array_equal(g, w))
            continue
        key = f"{p['agg']}_signal"
        want = want_all[float(p["signal_above"])]
        if control:
            got = {key: np.asarray(
                [low_all[float(p["signal_above"])][p["agg"]]])}
        if key not in got:
            mismatch += 1
            continue
        g = float(np.asarray(got[key]).reshape(-1)[0])
        w = want[p["agg"]]
        if p["agg"] == "avg":
            worst = max(worst, abs(g - w) / want["scale"])
        else:
            mismatch += int(g != w)
    out = {"errors": float(res["failed"]), "mismatch": float(mismatch)}
    if fam == "array":
        out["avg_err"] = float(worst)
    return out


def check(st: State, res: Dict, log) -> Dict[str, float]:
    st.rows = release(st)
    log(f"compared answers: {len(res['kept'])}")
    return _compare(st, res, control=False)


def control(st: State, res: Dict, log) -> Dict[str, float]:
    """The reference over the data rounded to bfloat16, in the
    program's place."""
    return _compare(st, res, control=True)
