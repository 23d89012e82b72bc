"""Standing ``bdml`` scoring of ICU beds through the front door, as an
open loop at the ward's real rate.

Every ``batch_period_s`` one batch arrives: ``period x hz`` ABP samples
of every bed, each bed on a stream of its own (``icu.bed<k>_abp``).  The
harness appends the batch, ticks the stream runtime, and every tenant
polls its subscriptions.  Each bed has one standing
``bdml(infer(window(icu.bed<k>_abp, W), models.scorer, field=abp))`` at
the traffic's ``every_n_ticks``, subscribed by every tenant; the
tenants of a bed share one execution.  Every bed is due on the same
tick, so each due tick runs a wave of one ``(1, W)`` forward per bed.
A delivery's latency runs from its batch's scheduled arrival to the
tenant holding the score on the host, so a wave that overruns delays
every later batch and the tail shows it.

The weights are the benchmark's (``reference/jamba.py``: ``weight``),
drawn from ``--seed`` and handed to the program in its param tree
(``program_params``); the reference draws the same ones again for the
check.  The program is asked for the configuration's architecture first
of all: one that does not know it fails at once.

The check, after the window with the deployment closed: every
delivery's ``window``/``rows`` columns; for a seed-drawn sample of
scored (batch, bed) pairs, the delivered scores against the float32
reference's (``reference/jamba.py``) on the bed's window as appended;
for the first ``rerun_windows`` of them, the program's own jitted
forward run again on the window's tokens, which must rebuild the
delivered score bitwise, and whose logits are compared with the
reference's at every position.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import answers, data
from chipbench.harness import annotate
from chipbench.reference import jamba as ref


@dataclasses.dataclass
class State:
    cfg: Dict
    traffic: Dict
    seed: int
    bd: Any
    door: Any
    handle: Any
    subs: List[Tuple[int, Any]]          # (bed, subscription)
    streams: List[Any]
    rng: np.random.Generator
    abp: List[List[np.ndarray]]          # per bed, every batch appended
    loaded: Any = None                   # the timed path's model, kept
    refs: Dict = dataclasses.field(default_factory=dict)


def sizes(cfg: Dict, traffic: Dict) -> Dict[str, int]:
    return {"beds": int(traffic["beds"]),
            "spb": int(round(cfg["hz"] * cfg["batch_period_s"])),
            "window": int(cfg["window"]), "capacity": int(cfg["ring_rows"]),
            "every": int(traffic["every_n_ticks"])}


def bql(stream: str, window: int) -> str:
    return (f"bdml(infer(window({stream}, {window}), models.scorer,"
            f" field=abp))")


def setup(cfg, traffic, seed, devices, log) -> State:
    from repro.core.api import default_deployment
    from repro.serve.frontdoor import FrontDoor
    from repro.stream.spec import StreamSpec

    from repro.stream.ml import resolve_arch

    resolve_arch(cfg["arch"])
    z = sizes(cfg, traffic)
    bd = default_deployment()
    wseed = data.sub_seed(seed, "weights")
    handle = bd.register_model("scorer", arch=cfg["arch"], seed=wseed,
                               params=program_params(cfg, wseed))
    names = [f"icu.bed{k}_abp" for k in range(z["beds"])]
    for name in names:
        bd.register_stream("streamstore0", StreamSpec(
            name, ("t", "abp"), capacity=z["capacity"]))
    door = FrontDoor(bd, stream_engine="streamstore0", **cfg["front_door"])
    sessions = [door.open_session(t) for t in traffic["tenants"]]
    subs = [(k, s.subscribe(bql(name, z["window"]), z["every"]))
            for k, name in enumerate(names) for s in sessions]
    log(f"subscriptions: {len(subs)} over {len(door._shared)} shared "
        f"queries, {z['beds']} beds")
    engine = bd.engines["streamstore0"]
    st = State(cfg, traffic, seed, bd, door, handle, subs,
               [engine.get(n) for n in names],
               np.random.default_rng(data.sub_seed(seed, "feed")),
               [[] for _ in names])
    # warm-up: fill every ring to its first complete window without
    # ticking, then tick through one wave (weights drawn, every program
    # compiled) and stop right after it, so the window starts one tick
    # past a wave and holds a whole number of waves
    for _ in range(-(-z["window"] // z["spb"])):
        append(st)
    while True:
        append(st)
        st.bd.streams.tick()
        got = [value for _, sub in subs for _, value in sub.poll()]
        for value in got:
            answers.to_host(value)
        if st.bd.streams.ticks % z["every"] == 0:
            break
    log(f"warm-up: {len(st.abp[0])} batches, {len(got)} deliveries on "
        f"the wave")
    return st


def program_params(cfg: Dict, seed: int) -> Dict:
    """The reference's weights of ``seed`` in the program's param tree
    (``repro.models.lm``): layer ``l`` is sub-layer ``l % P`` of scanned
    block ``l // P``, ``P`` the attention period, each weight stacked
    over the blocks; attention's matrices split into heads, norm scales
    under ``scale``."""
    import jax.numpy as jnp

    z = ref.sizes(cfg)
    period, blocks = z.attn_period, z.layers // z.attn_period
    heads = {"wq": (z.heads, z.head_dim), "wk": (z.kv_heads, z.head_dim),
             "wv": (z.kv_heads, z.head_dim)}

    def leaf(layer: int, name: str):
        w = ref.weight(cfg, seed, layer, name)
        part = name.rpartition(".")[2]
        if name.startswith("mixer.") and part in heads:
            return w.reshape(w.shape[0], *heads[part])
        if name == "mixer.wo":
            return w.reshape(z.heads, z.head_dim, w.shape[1])
        return w

    tree: Dict[str, Any] = {
        "embed": {"embedding": ref.weight(cfg, seed, -1, "embed")},
        "final_norm": {"scale": ref.weight(cfg, seed, -1, "final_norm")},
        "blocks": {}}
    for i in range(period):
        sub: Dict[str, Any] = {}
        for name in ref.shapes(cfg, i):
            group, _, part = name.rpartition(".")
            w = jnp.stack([leaf(b * period + i, name)
                           for b in range(blocks)])
            if group:
                sub.setdefault(group, {})[part] = w
            else:
                sub[name] = {"scale": w}
        tree["blocks"][f"sub{i}"] = sub
    return tree


def append(st: State) -> None:
    z = sizes(st.cfg, st.traffic)
    k = len(st.abp[0])
    rows = data.ward_batch(st.rng, k, z["beds"], z["spb"], st.cfg["hz"])
    t = rows["t"].reshape(z["spb"], z["beds"])
    abp = rows["abp"].reshape(z["spb"], z["beds"])
    for bed, stream in enumerate(st.streams):
        stream.append({"t": t[:, bed], "abp": abp[:, bed]})
        st.abp[bed].append(abp[:, bed])


def run(st: State, seconds: float, probe, log) -> Dict[str, Any]:
    z = sizes(st.cfg, st.traffic)
    period = st.cfg["batch_period_s"]
    n = max(1, int(round(seconds / period)))
    lat: List[float] = []
    late: List[float] = []
    kept: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
    waves: List[List[float]] = []   # [start lateness, slowest], ms
    failed = due_total = 0
    done = {"ticks": 0, "deliveries": 0, "waves": 0}
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
        scoring = tick_no % z["every"] == 0
        upto = len(st.abp[0]) - 1
        if scoring:
            waves.append([1e3 * late[-1], 0.0])
        with annotate("poll"):
            for j, (_, sub) in enumerate(st.subs):
                got = sub.poll()
                if not scoring:
                    failed += len(got)           # a delivery not due
                    continue
                due_total += 1
                if len(got) != 1 or got[0][0] != tick_no:
                    failed += 1
                    continue
                kept[(upto, j)] = answers.to_host(got[0][1])
                lat.append(time.perf_counter() - due)
                waves[-1][1] = max(waves[-1][1], 1e3 * lat[-1])
        done["ticks"] += 1
        done["deliveries"] = len(lat)
        done["waves"] += int(scoring)
        probe.step()
    log(f"deliveries: {len(lat)} of {due_total} due, over {n} ticks and "
        f"{done['waves']} waves")
    log(f"generator_late_ms: p50 {1e3 * float(np.median(late))} max "
        f"{1e3 * max(late)}")
    log("waves_ms (start lateness, slowest delivery): "
        + " ".join(f"{a:.1f},{b:.1f}" for a, b in waves))
    return {"e2e": {"event_to_result_p95_ms": 1e3 * answers.p95(lat)}
            if lat else {},
            "attempted": due_total, "failed": failed, "kept": kept,
            "late": late, "waves": waves}


def release(st: State) -> None:
    """Close the deployment; take the timed path's model (its weights
    and jitted forward) out of the island's cache and keep it for the
    check alone, so that it is freed with the run's state."""
    from repro.stream import ml
    h = st.handle
    st.loaded = ml.unload_model(h.arch, h.seed, h.reduced)
    st.door.close()
    st.bd = st.door = st.streams = None
    gc.collect()


def window_values(st: State, bed: int, upto: int) -> np.ndarray:
    """The bed's last complete tumbling window once batch ``upto`` was
    appended, rows ``[k W, (k + 1) W)`` of its feed, as the stream
    island serves window views: float32 (its precision contract), which
    the scorer bins into token ids."""
    z = sizes(st.cfg, st.traffic)
    rows = np.concatenate(st.abp[bed][:upto + 1])
    k = rows.shape[0] // z["window"] - 1
    return rows[k * z["window"]:(k + 1) * z["window"]].astype(np.float32)


def sample(st: State, res: Dict) -> List[Tuple[int, int]]:
    """A seed-drawn sample of the scored (batch, bed) pairs."""
    pairs = sorted({(upto, st.subs[j][0]) for upto, j in res["kept"]})
    rng = np.random.default_rng(data.sub_seed(st.seed, "check"))
    take = min(int(st.traffic["check_windows"]), len(pairs))
    return [pairs[i] for i in sorted(rng.choice(len(pairs), take,
                                                replace=False))]


def reference(st: State, upto: int, bed: int):
    """(token ids, float32 reference logits) of one scored window, kept
    for the control."""
    if (upto, bed) not in st.refs:
        toks = ref.tokens(window_values(st, bed, upto),
                          st.cfg["vocab_size"])
        st.refs[(upto, bed)] = (toks, np.asarray(ref.forward(
            st.cfg, weights_seed(st), toks)))
    return st.refs[(upto, bed)]


def weights_seed(st: State) -> int:
    return data.sub_seed(st.seed, "weights")


def _delivered(st: State, res: Dict, upto: int, bed: int) -> List[float]:
    return [float(v["score"][0]) for (u, j), v in res["kept"].items()
            if u == upto and st.subs[j][0] == bed]


def check(st: State, res: Dict, log) -> Dict[str, float]:
    import jax.numpy as jnp
    from repro.stream import ml

    release(st)
    z = sizes(st.cfg, st.traffic)
    mismatch = sum(
        not (np.array_equal(v["window"], [0])
             and np.array_equal(v["rows"], [z["window"]])
             and v["score"].shape == (1,))
        for v in res["kept"].values())
    pairs = sample(st, res)
    score_err = logit_err = 0.0
    rerun_mismatch = 0
    for i, (upto, bed) in enumerate(pairs):
        toks, want = reference(st, upto, bed)
        nll = ref.score(want, toks)
        got = _delivered(st, res, upto, bed)
        score_err = max([score_err] + [abs(g - nll) for g in got])
        if i < int(st.traffic["rerun_windows"]):
            prog = ml.quantize(window_values(st, bed, upto),
                               st.loaded.cfg.vocab_size)
            logits = np.asarray(st.loaded.forward(
                st.loaded.params, jnp.asarray(prog[None], jnp.int32))[0])
            rebuilt = float(ml.score_tokens(st.loaded, prog))
            rerun_mismatch += sum(g != rebuilt for g in got)
            logit_err = max(logit_err, float(np.max(np.abs(logits - want))
                                             / np.std(want)))
    log(f"compared deliveries: {len(res['kept'])} (columns), "
        f"{len(pairs)} windows (scores)")
    return {"missing": float(res["failed"]), "mismatch": float(mismatch),
            "rerun_mismatch": float(rerun_mismatch), "score_err": score_err,
            "logit_err": logit_err}


def control(st: State, res: Dict, log) -> Dict[str, float]:
    """The reference with weights and matmul inputs rounded to float8
    e4m3 in the program's place (after ``check``)."""
    import jax.numpy as jnp

    low = jnp.float8_e4m3fn
    score_err = logit_err = 0.0
    for i, (upto, bed) in enumerate(sample(st, res)):
        toks, want = reference(st, upto, bed)
        got = np.asarray(ref.forward(st.cfg, weights_seed(st), toks, low))
        score_err = max(score_err,
                        abs(ref.score(got, toks) - ref.score(want, toks)))
        if i < int(st.traffic["rerun_windows"]):
            logit_err = max(logit_err, float(np.max(np.abs(got - want))
                                             / np.std(want)))
    return {"missing": float(res["failed"]), "mismatch": 0.0,
            "rerun_mismatch": 0.0, "score_err": score_err,
            "logit_err": logit_err}
