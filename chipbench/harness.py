"""The part of a run every cell shares: the files a cell is made of,
the measured window, the traced window and its reduction, the result
line.

A cell is found by name: ``BENCHMARK.json`` names its configuration
(``configs/<config>.json``) and its traffic (``traffic/<traffic>.json``);
the traffic names its generator (``generators/<generator>.py``), the one
generator of that kind of load; the limits of its correctness check
are ``limits/<cell>.json``; a per-layer metric is read by
``metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files
and edits none: a reader sees the deltas of every series of the
program's metrics registry over the traced part, and declares any other
counter it needs (``counters()``) in its own file.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

# programs the window may fetch are counted; none should be
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",)


def load(kind: str, name: str) -> Dict[str, Any]:
    """``chipbench/<kind>/<name>.json``."""
    with open(HERE / kind / f"{name}.json") as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, not a default."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(have {sorted(table)})")
    return table[device_kind]


def tpu_devices(cell: Dict[str, Any], log) -> Optional[list]:
    """Set the process up for one cell's config, then JAX's devices for
    the cell, or None where they are not TPU chips enough.  Call it
    before anything imports jax: it persists every program, however
    quick to compile, so only a checkout's first run of a cell
    compiles."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    os.environ.setdefault("REPRO_TRACE_RING", "1000000")
    os.environ.update(load("configs", cell["config"]).get("env", {}))
    import jax
    devices = jax.devices()
    log(f"platform: {devices[0].platform}")
    log(f"device_kind: {devices[0].device_kind}")
    log(f"device_count: {len(devices)}")
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"{cell['name']} needs {cell['chips']} TPU chip(s); jax "
            f"found {len(devices)} {devices[0].platform} device(s)")
        return None
    from repro.stream import compile as qc
    log(f"compile_cache: {qc.use_compile_cache()}")
    return devices[:cell["chips"]]


def cell(name: str) -> Dict[str, Any]:
    return {w["name"]: w for w in benchmark()["workloads"]}[name]


def generator(name: str):
    return importlib.import_module(f"chipbench.generators.{name}")


def reader(metric: str):
    """The module ``metrics/<metric>.py``: ``read(ctx)`` returns the
    metric or None, and an optional ``counters()`` returns the numbers
    it needs besides the registry's, read at the start and the end of
    the traced part (``ctx["own"]`` holds their deltas)."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def registry() -> Dict[str, Any]:
    """Every series of the program's metrics registry as numbers:
    a histogram as (count, sum), a counter or gauge as its value, keyed
    ``name`` or ``name{label=value,...}``."""
    from repro.obs import metrics
    out: Dict[str, Any] = {}
    for name, fam in metrics.snapshot().items():
        for row in fam["series"]:
            labels = ",".join(f"{k}={v}" for k, v in
                              sorted(row["labels"].items()))
            key = f"{name}{{{labels}}}" if labels else name
            out[key] = (row["count"], row["sum"]) \
                if fam["type"] == "histogram" else row["value"]
    return out


def delta(after: Dict[str, Any], before: Dict[str, Any]) -> Dict[str, Any]:
    """``after - before`` per key (a pair term by term); a key that
    appeared in between counts from 0."""
    out: Dict[str, Any] = {}
    for k, a in after.items():
        b = before.get(k)
        if isinstance(a, tuple):
            b = b or (0,) * len(a)
            out[k] = tuple(x - y for x, y in zip(a, b))
        elif isinstance(a, (int, float)) and not isinstance(a, bool):
            out[k] = a - (b or 0)
    return out


def annotate(name: str):
    """A host span on the profiler's clock (``bench/<name>``)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench/{name}")


class Compiles:
    """Counts programs compiled or fetched from the persistent cache."""

    def __init__(self) -> None:
        import jax
        self.compiled = 0
        self.fetched = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.compiled += 1

    def _event(self, name, **_):
        if name in CACHE_EVENTS:
            self.fetched += 1

    def total(self) -> int:
        return self.compiled + self.fetched


class Pauses:
    """Python's garbage-collector pauses, from ``gc.callbacks``."""

    def __init__(self) -> None:
        self.times: List[float] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.times.append(time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


class Probe:
    """The measured window and, with ``trace``, the traced window at its
    start: the profiler, the program's spans and metrics registry, the
    counters the cell's readers declare, and the generator's work
    counters, read at the start and at the end of the traced part."""

    def __init__(self, trace: bool, trace_seconds: float,
                 compiles: Compiles, own: Optional[Dict[str, Callable]]
                 = None) -> None:
        self.trace = trace
        self.trace_seconds = trace_seconds
        self.compiles = compiles
        self.own = own or {}
        self.pauses: Optional[Pauses] = None
        self.dir: Optional[str] = None
        self._ann = None
        self._work: Callable[[], Dict[str, float]] = dict
        self.before: Dict[str, Any] = {}
        self.after: Dict[str, Any] = {}
        self.t0 = self.t1 = 0.0
        self.traced = (0.0, 0.0)
        self.compiles_in_window = 0

    def _counters(self) -> Dict[str, Any]:
        return {"registry": registry(), "work": dict(self._work()),
                "own": {m: dict(f()) for m, f in self.own.items()}}

    def begin(self, work: Callable[[], Dict[str, float]] = dict) -> None:
        self._work = work
        self._c0 = self.compiles.total()
        self.pauses = Pauses()
        if self.trace:
            import jax
            from repro.obs import trace
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            trace.reset()
            trace.set_enabled(True)
            self.before = self._counters()
            jax.profiler.start_trace(self.dir)
            self._ann = annotate("window")
            self._ann.__enter__()
            self.traced = (time.perf_counter(), 0.0)
        self.t0 = time.perf_counter()

    def step(self) -> None:
        """Called after every unit of work; ends the traced part once it
        has lasted ``trace_seconds``."""
        if self._ann is not None and \
                time.perf_counter() - self.t0 >= self.trace_seconds:
            self._stop()

    def _stop(self) -> None:
        import jax
        from repro.obs import trace
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.traced = (self.traced[0], time.perf_counter())
        self.after = self._counters()
        self.spans = [s for s in trace.spans()
                      if s.start >= self.traced[0]
                      and s.start + s.duration <= self.traced[1]]
        trace.set_enabled(False)
        jax.profiler.stop_trace()

    def end(self) -> None:
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._stop()
        self.compiles_in_window = self.compiles.total() - self._c0
        if self.pauses is not None:
            self.pauses.close()

    def reduce(self) -> Optional[Dict[str, Any]]:
        """The device busy time, idle gaps and top operations of the
        traced part; None where the trace holds no device operation."""
        if self.dir is None:
            return None
        from chipbench import traces
        try:
            device, host = traces.read_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        marks = [(s, s + d) for n, s, d in host if n == "bench/window"]
        if not marks:
            return None
        return traces.reduce_events(
            device, [h for h in host if h[0] != "bench/window"], marks[-1])

    def context(self, cell, cfg, traffic, device_kind) -> Dict[str, Any]:
        """What a per-layer metric reads: deltas over the traced part."""
        b, a = self.before, self.after
        return {"cell": cell, "config": cfg, "traffic": traffic,
                "window_s": self.traced[1] - self.traced[0],
                "spans": self.spans,
                "registry": delta(a["registry"], b["registry"]),
                "own": {m: delta(a["own"][m], b["own"][m])
                        for m in a["own"]},
                "work": delta(a["work"], b["work"]),
                "device": self.reduced, "device_kind": device_kind}


def memory_peak(devices) -> Optional[int]:
    peaks_ = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devices]
    return max(peaks_) if peaks_ else None


def e2e_names(bench, cell: str) -> List[Dict[str, Any]]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def layer_names(bench, cell: str) -> List[Dict[str, Any]]:
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def run_cell(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
             devices, t_start: float, log=print, cfg=None,
             traffic=None, limits=None) -> Dict[str, Any]:
    """Set up, warm, measure, free, check: one run of ``cell`` on
    ``devices``.  Returns the result line as a dict.  ``cfg``,
    ``traffic`` and ``limits`` replace the cell's files (tests run
    cells small)."""
    bench = benchmark()
    cfg = cfg or load("configs", cell["config"])
    traffic = traffic or load("traffic", cell["traffic"])
    limits = limits or load("limits", cell["name"])
    drv = generator(traffic["generator"])
    readers = {m["name"]: reader(m["name"])
               for m in layer_names(bench, cell["name"])} if trace else {}
    compiles = Compiles()
    state = drv.setup(cfg, traffic, seed, devices, log)
    probe = Probe(trace, float(traffic.get("trace_seconds", 5.0)),
                  compiles, {n: r.counters for n, r in readers.items()
                             if hasattr(r, "counters")})
    # what set-up built lives as long as the run, as a long-running
    # server's start-up does: the collector need not walk it again
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s: {setup_s}")
    try:
        res = drv.run(state, seconds, probe, log)
        probe.end()
    finally:
        gc.unfreeze()
    log(f"window_s: {probe.t1 - probe.t0}")
    log(f"compiles_in_window: {probe.compiles_in_window}")
    pauses = probe.pauses.times
    log(f"gc_pauses: {len(pauses)}, longest "
        f"{1e3 * max(pauses, default=0.0)} ms, total "
        f"{1e3 * sum(pauses)} ms")
    mem = memory_peak(devices)
    if mem and devices[0].platform == "tpu":
        log(f"memory_peak_share: "
            f"{100.0 * mem / peaks(devices[0].device_kind)['hbm_bytes']} %")
    probe.reduced = probe.reduce() if trace else None
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        ctx = probe.context(cell["name"], cfg, traffic,
                            devices[0].device_kind)
        for m in layer_names(bench, cell["name"]):
            value = readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e_names(bench, cell["name"]):
            value = setup_s if m["name"] == "setup_s" else \
                res["e2e"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    readings = drv.check(state, res, log)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in
              readings.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values()) \
        and set(readings) == set(limits)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    if trace and probe.reduced is not None:
        device["busy_s"] = probe.reduced["busy_s"]
        device["window_s"] = probe.reduced["window_s"]
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": res["attempted"],
                            "failed": res["failed"], "metrics": metrics,
                            "device": device}
    if trace and probe.reduced is not None:
        line["breakdown"] = {"device_ops": probe.reduced["device_ops"],
                             "idle_gaps": probe.reduced["idle_gaps"]}
    line["checks"] = checks
    return line
