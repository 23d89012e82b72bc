"""ML inference island (``bdml``): score stream windows through the model
registry.

The island has one operation:

  infer(window(S, n), models.M[, field=f])    -> dm.Table
  infer(ewindow(S, span), models.M[, field=f])
  infer(W, models.M[, field=f])               (W: a window view already
                                              on this engine, e.g.
                                              bdcast-delivered)

Each window's chosen field is quantized into token ids (deterministic
per-window min/max binning over the float64 row values — the same rows
always produce the same tokens, on any shard layout or replay) and run
through ``registry.forward`` — on the reduced config for the short
aliases (``lm``/``moe``/``rwkv6``/``mamba``), on the published config
for an explicit registry arch name, with its weights held in
bfloat16 (``weight_specs``); the score is
the mean next-token NLL in float32 — an anomaly signal: windows the
model finds unlikely score high.  The result is a relational Table with
one row per window (``window``/``rows``/``score``), so scores ride the
existing staged casts into any island.

Bit-identity contract (the house invariant):

  * ``infer`` over a gathered window ≡ a direct ``registry.forward`` on
    the same rows, **bitwise** — the forward is jit-compiled, and on the
    reduced configs jit ≡ eager is exact; the NLL is computed eagerly in
    f32 from the returned logits, so a test can rebuild the score from
    ``registry.forward`` alone and demand ``err == 0.0``.
  * sharded ≡ unsharded and replayed ≡ original: window gathers are
    bit-identical across shard layouts (stream island contract), params
    come from a fixed PRNG seed cached per (arch, seed), and every
    window executes at the same canonical ``(1, rows)`` batch shape, so
    a score never depends on what else shares its wave (the same
    batch-composition independence the dropless MoE path guarantees).

Execution rides the serve tier's wave model (``TickWaveScheduler``): all
standing ``infer`` queries that run within one StreamRuntime tick join a
single wave — N standing queries cost one wave per tick, sharing the
params/jit caches — with ``ml/wave`` / ``ml/score`` (arch, rows) spans
and ``repro_ml_*`` metrics (``repro_ml_tokens_scored_total`` by arch,
``repro_ml_param_bytes`` per loaded model, and per scored window its
Mamba layers under ``repro_ml_scan_kernel_total`` or
``repro_ml_scan_reference_total``, by the path its forward's scan
took).  ``StreamRuntime.tick`` mirrors ``stats()`` into
``Monitor.observe_ml`` so ``admin.status()["ml"]`` tracks it live.

Model handles are registered via ``BigDawg.register_model`` on an
``MLEngine`` (``bd.ensure_ml_engines``); the Planner pins ``infer``
reads to the model's home engine.  Errors about not-yet-complete
windows propagate as ``StreamException`` (transient: standing queries
and cached plans survive them); a missing jax is reported the same
transient way and counted in ``stats()["fallbacks"]``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core import datamodel as dm
from repro.core.engines import Engine
from repro.obs import metrics, trace
from repro.stream.engine import (SEQ_FIELD, Stream, StreamEngine,
                                 StreamException)

try:  # pragma: no cover - exercised by monkeypatching JAX_AVAILABLE
    import jax
    import jax.numpy as jnp
    from repro.kernels import interpret_mode
    from repro.kernels.mamba_scan import ops as scan_ops
    from repro.models import mamba, registry
    from repro.serve.engine import TickWaveScheduler
    from repro.sharding import logical as _logical
    JAX_AVAILABLE = True
except ModuleNotFoundError as exc:  # any other import error raises
    if exc.name != "jax":
        raise
    jax = jnp = registry = _logical = None
    interpret_mode = scan_ops = mamba = None
    TickWaveScheduler = None
    JAX_AVAILABLE = False


class MLException(StreamException):
    """ml-island failure; subclasses the streaming island's transient
    marker so standing queries and cached plans survive it."""


# registry architectures behind the island's short model aliases (there
# is no pure-mamba arch in the pool; jamba2-3b is the mamba-hybrid)
ALIASES = {"lm": "qwen2-1.5b", "moe": "olmoe-1b-7b",
           "rwkv6": "rwkv6-7b", "mamba": "jamba2-3b"}


def resolve_arch(name: str) -> str:
    if name in ALIASES:
        return ALIASES[name]
    if registry is not None and name in registry.ARCH_NAMES:
        return name
    if registry is None and name:  # jax absent: defer validation
        return name
    raise MLException(
        f"unknown model {name!r}: aliases {sorted(ALIASES)} or a "
        f"registry arch name")


@dataclasses.dataclass
class MLModel:
    """Catalog handle for a registered model.  Dotted ``name`` on
    purpose: the Planner's signature extractor treats dotted tokens as
    referenced objects, which is what pins infer reads to this handle's
    home engine."""
    name: str                      # catalog object name, e.g. models.moe
    arch: str                      # registry architecture
    seed: int = 0                  # PRNG seed for the cached params
    home_engine: str = "mlhost0"
    reduced: bool = True           # registered by alias: reduced preset

    def nbytes(self) -> int:
        return 0                   # the handle itself holds no tensors


class MLEngine(Engine):
    """Model-serving engine of the ml island.  Stores ``MLModel``
    handles (plus any bdcast-delivered window views); keeps
    back-references to the deployment so ``infer`` can resolve inline
    window expressions against the stream's home StreamEngine and join
    the current tick's wave."""
    kind = "mlserve"
    islands: Tuple[str, ...] = ("ml",)

    def __init__(self, name: str, runtime=None, engines=None,
                 mesh=None, rules=None) -> None:
        super().__init__(name, mesh, rules)
        self.runtime = runtime            # StreamRuntime (tick counter)
        self.deployment_engines = engines  # name -> Engine


@dataclasses.dataclass
class _Loaded:
    cfg: Any
    params: Any
    forward: Any                   # jitted (params, tokens) -> logits


_LOADED: Dict[Tuple[str, int, bool], _Loaded] = {}
_WAVE = TickWaveScheduler() if TickWaveScheduler is not None else None
_STATS: Dict[str, int] = {
    "models_loaded": 0, "params_cache_hits": 0, "infer_executions": 0,
    "windows_scored": 0, "fallbacks": 0}


def stats() -> Dict[str, Any]:
    """Process-wide ml-island counters (the Monitor/admin block)."""
    out: Dict[str, Any] = {"jax_available": JAX_AVAILABLE, **_STATS}
    out["waves"] = _WAVE.waves if _WAVE is not None else 0
    out["wave_submissions"] = (_WAVE.submissions
                               if _WAVE is not None else 0)
    return out


def weight_specs(cfg):
    """The model's ParamSpec tree with every weight held in bfloat16: the
    dtype each matmul casts its weights to already.  Norm scales (1)
    stay exact; the scan's ``a_log`` and dt bias are the bfloat16
    roundings of their draws, as every other weight is."""
    return jax.tree.map(
        lambda s: dataclasses.replace(s, dtype=jnp.bfloat16),
        registry.param_specs(cfg),
        is_leaf=lambda x: isinstance(x, _logical.ParamSpec))


def load_model(arch: str, seed: int = 0, reduced: bool = True,
               params: Any = None) -> _Loaded:
    """The per-(arch, seed, reduced) params + jitted-forward cache.
    Params are derived from a fixed PRNGKey, so every deployment that
    registers the same model scores with bit-identical weights; they are
    drawn a leaf at a time and held in bfloat16 (``weight_specs``).

    ``params`` (the registry's param tree of the arch, any float dtype)
    are served in place of the seed's draw: they replace the key's
    cache entry, held in bfloat16 like a draw.  A tree of another
    structure or leaf shape is refused."""
    key = (arch, seed, reduced)
    if params is None and key in _LOADED:
        _STATS["params_cache_hits"] += 1
        return _LOADED[key]
    cfg = registry.get_config(arch, reduced=reduced)
    specs = weight_specs(cfg)
    if params is None:
        params = _logical.init_params(jax.random.PRNGKey(seed), specs)
    else:
        params = _given_params(arch, params, specs)
    fwd = jax.jit(lambda p, toks: registry.forward(
        p, {"tokens": toks}, cfg, None)[0])
    loaded = _Loaded(cfg=cfg, params=params, forward=fwd)
    _LOADED[key] = loaded
    _STATS["models_loaded"] += 1
    _observe_cache(key, sum(a.nbytes for a in jax.tree.leaves(params)))
    return loaded


def _given_params(arch: str, params: Any, specs: Any) -> Any:
    """``params`` checked leaf by leaf against ``specs`` and cast to the
    specs' dtype."""
    is_spec = lambda x: isinstance(x, _logical.ParamSpec)  # noqa: E731
    want = jax.tree.structure(specs, is_leaf=is_spec)
    if jax.tree.structure(params) != want:
        raise MLException(f"params for {arch!r} do not have the arch's "
                          f"param tree: got {jax.tree.structure(params)}, "
                          f"want {want}")
    bad = [jax.tree_util.keystr(path) for (path, a), s in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(specs, is_leaf=is_spec))
        if tuple(a.shape) != tuple(s.shape)]
    if bad:
        raise MLException(f"params for {arch!r}: leaves of the wrong "
                          f"shape: {bad}")
    return jax.tree.map(lambda a, s: jnp.asarray(a, s.dtype), params,
                        specs, is_leaf=is_spec)


def unload_model(arch: str, seed: int = 0, reduced: bool = True
                 ) -> Optional[_Loaded]:
    """Take one (arch, seed, reduced) entry out of the params cache and
    return it: its weights are freed once no caller holds them.  The
    next ``load_model`` of the key draws them again, bit-identically."""
    key = (arch, seed, reduced)
    loaded = _LOADED.pop(key, None)
    _observe_cache(key, 0)
    return loaded


def _observe_cache(key: Tuple[str, int, bool], nbytes: int) -> None:
    metrics.gauge("repro_ml_models_loaded",
                  "(arch, seed) entries in the params cache").set(
        len(_LOADED))
    arch, seed, reduced = key
    metrics.gauge("repro_ml_param_bytes",
                  "bytes of weights resident per loaded model", arch=arch,
                  seed=seed, reduced=reduced).set(nbytes)


def quantize(values: np.ndarray, vocab: int) -> np.ndarray:
    """Deterministic per-window tokenization: min/max binning of the
    float64 row values into ``vocab`` ids.  A pure function of the row
    values alone — the same rows quantize identically on any shard
    layout, backend or replay."""
    v = np.asarray(values, np.float64).reshape(-1)
    lo, hi = float(v.min()), float(v.max())
    if hi <= lo:
        return np.zeros(v.shape[0], np.int32)
    ids = np.floor((v - lo) / (hi - lo) * (vocab - 1))
    return np.minimum(ids, vocab - 1).astype(np.int32)


def score_tokens(loaded: _Loaded, tokens: np.ndarray):
    """Mean next-token NLL of one window's token ids, float32.  The
    forward runs jitted at the canonical (1, rows) shape; the NLL is
    computed eagerly from the logits — both bitwise-reproducible, so
    rebuilding this from a direct ``registry.forward`` matches exactly."""
    if tokens.shape[0] < 2:
        raise MLException(
            f"window too short to score: {tokens.shape[0]} row(s), "
            f"need >= 2")
    toks = jnp.asarray(tokens[None, :], jnp.int32)
    logits = loaded.forward(loaded.params, toks)
    logp = jax.nn.log_softmax(logits[0, :-1].astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, toks[0, 1:, None], -1)[..., 0]
    return nll.mean()


def _count_scans(loaded: _Loaded, windows: List[np.ndarray]) -> None:
    """Add each window's Mamba layers to the counter of the path its
    forward's scan took, decided by ``scan_ops.uses_kernel``: the rule
    ``apply_mamba`` dispatches by, on the platform the weights live on
    and the window's length."""
    cfg = loaded.cfg
    layers = (sum(m == "mamba" for m, _ in cfg.layer_plan())
              * cfg.num_scanned())
    if not layers:
        return
    leaf = jax.tree.leaves(loaded.params)[0]
    platform = "cpu" if interpret_mode(leaf) else "tpu"
    for w in windows:
        if scan_ops.uses_kernel(platform, int(w.shape[0]),
                                mamba.d_inner(cfg)):
            metrics.counter("repro_ml_scan_kernel_total",
                            "Mamba layers of scored windows scanned by "
                            "the Pallas kernel").inc(layers)
        else:
            metrics.counter("repro_ml_scan_reference_total",
                            "Mamba layers of scored windows scanned by "
                            "the reference lax.scan").inc(layers)


# ---------------------------------------------------------------------------
# the shim: infer(<window expr | name>, <model>[, field=...])
# ---------------------------------------------------------------------------
_WINDOW_EXPR_RE = re.compile(r"^(window|ewindow)\s*\(\s*([\w\.]+)\s*,",
                             re.IGNORECASE)
_KWARG_RE = re.compile(r"^(\w+)\s*=\s*(.+)$")


def _find_stream_engine(engines: Dict[str, Engine],
                        name: str) -> Optional[StreamEngine]:
    for ename in sorted(engines):
        e = engines[ename]
        if (isinstance(e, StreamEngine) and e.has(name)
                and isinstance(e.get(name), Stream)):
            return e
    return None


def _window_values(engine: MLEngine, expr: str,
                   field: Optional[str]) -> Tuple[List[np.ndarray], int]:
    """Evaluate the window argument to a list of per-window float64 row
    vectors (1 for tumbling/ewindow views, N for sliding 2-D views)."""
    m = _WINDOW_EXPR_RE.match(expr)
    if m:
        sname = m.group(2)
        engines = engine.deployment_engines or {}
        home = _find_stream_engine(engines, sname)
        if home is None:
            raise MLException(f"stream {sname!r} not found on any "
                              f"StreamEngine")
        from repro.stream.shim import execute_stream
        view = execute_stream(home, expr)
        ts_field = getattr(home.get(sname), "ts_field", None)
    elif engine.has(expr):
        view = engine.get(expr)
        ts_field = None
    else:
        raise MLException(
            f"infer needs a window(...)/ewindow(...) expression or a "
            f"window object on {engine.name}; got {expr!r}")
    if not isinstance(view, dm.ArrayObject):
        raise MLException(f"infer scores window views (ArrayObject), "
                          f"got {type(view).__name__}")
    if field is None:
        skip = {ts_field, "ts", SEQ_FIELD, "seq"}
        field = next((a for a in view.attrs if a not in skip),
                     next(iter(view.attrs)))
    if field not in view.attrs:
        raise MLException(f"window has no field {field!r} "
                          f"(have {list(view.attrs)})")
    vals = np.asarray(view.attrs[field], np.float64)
    if vals.ndim == 1:
        return [vals], 1
    # sliding windows: dims ("window", "tick") — one score per row
    return [vals[i] for i in range(vals.shape[0])], vals.shape[0]


def _wave_key(engine: MLEngine) -> Tuple[int, int]:
    """All infer executions between two ticks of the same deployment
    share one wave; the tick counter advances before standing queries
    run, so every standing query due on a tick lands in that tick's
    wave."""
    rt = engine.runtime
    return (id(rt), rt.ticks if rt is not None else 0)


def execute_ml(engine: Engine, query: str) -> dm.Table:
    q = query.strip()
    m = re.match(r"^(\w+)\s*\(", q)
    if not m or m.group(1).lower() != "infer":
        raise ValueError(f"unsupported ml op: {q!r}")
    if not isinstance(engine, MLEngine):
        raise MLException(f"ml island queries need an MLEngine, "
                          f"got {engine.name} ({engine.kind})")
    if not JAX_AVAILABLE:
        _STATS["fallbacks"] += 1
        metrics.counter("repro_ml_fallbacks_total",
                        "infer refused: jax unavailable").inc()
        raise MLException("ml island needs jax for registry.forward; "
                          "jax is unavailable in this process")
    from repro.stream.shim import _balanced, _split_args
    inner, _ = _balanced(q[m.end() - 1:])
    args = _split_args(inner)
    if len(args) < 2:
        raise MLException(f"infer needs (window, model), got {q!r}")
    kwargs: Dict[str, str] = {}
    pos = []
    for a in args:
        kw = _KWARG_RE.match(a)
        if kw and kw.group(1).lower() == "field":
            kwargs["field"] = kw.group(2).strip().strip("'\"")
        else:
            pos.append(a)
    window_expr, model_name = pos[0], pos[1].strip()
    if not engine.has(model_name):
        raise MLException(f"model {model_name!r} is not registered on "
                          f"{engine.name} (bd.register_model)")
    handle = engine.get(model_name)
    if not isinstance(handle, MLModel):
        raise MLException(f"{model_name!r} is not an MLModel handle")

    def run() -> dm.Table:
        loaded = load_model(handle.arch, handle.seed, handle.reduced)
        windows, n = _window_values(engine, window_expr, kwargs.get("field"))
        scores, rows = [], []
        for i, vals in enumerate(windows):
            with trace.span("ml/score", arch=handle.arch, window=i,
                            rows=int(vals.shape[0])):
                toks = quantize(vals, loaded.cfg.vocab_size)
                scores.append(trace.device_wait(score_tokens(loaded, toks)))
        _STATS["windows_scored"] += n
        metrics.counter("repro_ml_windows_scored_total",
                        "windows scored").inc(n)
        metrics.counter("repro_ml_tokens_scored_total",
                        "tokens of every scored window",
                        arch=handle.arch).inc(
            sum(int(w.shape[0]) for w in windows))
        _count_scans(loaded, windows)
        return dm.Table({
            "window": jnp.arange(n, dtype=jnp.int32),
            "rows": jnp.asarray([w.shape[0] for w in windows], jnp.int32),
            "score": jnp.stack(scores).astype(jnp.float32)})

    _STATS["infer_executions"] += 1
    metrics.counter("repro_ml_infer_total",
                    "infer executions (standing + ad hoc)").inc()
    return _WAVE.submit(_wave_key(engine), run)
