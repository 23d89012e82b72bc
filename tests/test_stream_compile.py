"""The compiled standing-query path (repro.stream.compile): every op
the jaxpr plan compiler lowers — tumbling/sliding windows, event-time
windows, rolling aggregates, the banded interval join — must be
**bit-identical** to the interpreter in shim.py: same values, same
dtypes, same column order, same error strings, same JOIN_STATS deltas.
That is the house invariant the jit-parity CI lane enforces; these
tests are its unit-level teeth.

Also covered: the plan cache (second execution is a cache hit, not a
recompile), the fallback taxonomy (out-of-family ops bump
``interpreted``, uncompilable family ops bump ``fallbacks`` with a
reason), and x64 hygiene (the compiled path must not flip the global
``jax_enable_x64`` switch).

Skips cleanly when jax is missing (the compiled path itself must also
*fall back* cleanly then — covered by test_backend_jit_without_jax)."""
import numpy as np
import pytest

from repro.core.api import default_deployment
from repro.stream import compile as qc
from repro.stream.engine import StreamException


@pytest.fixture(autouse=True)
def _fresh_stats():
    qc.reset_stats()
    yield
    qc.reset_stats()


def _deploy(rng):
    """One deployment with the full op-family zoo: a plain stream, an
    event-time stream, and a 2-shard colocated event-time pair."""
    bd = default_deployment()
    p = bd.register_stream("streamstore0", "c.p", ("v", "w"),
                           capacity=256)
    s = bd.register_stream("streamstore0", "c.s", ("ts", "x"),
                           capacity=256, ts_field="ts", max_delay=0.0)
    a = bd.register_stream("streamstore0", "c.a", ("ts", "x"),
                           capacity=256, ts_field="ts", max_delay=0.0,
                           shards=2, num_engines=2)
    b = bd.register_stream("streamstore0", "c.b", ("ts", "y"),
                           capacity=256, ts_field="ts", max_delay=0.0,
                           shards=2, num_engines=2)
    n = 96
    tiny = np.where(np.arange(n) % 5 == 0, 1e-40, 1.0)  # float32 subnormals
    p.append({"v": rng.normal(size=n) * tiny, "w": rng.normal(size=n)})
    ts = np.sort(rng.uniform(0, 50, size=n))
    s.append({"ts": ts, "x": rng.normal(size=n) * tiny})
    s.flush()
    a.append({"ts": ts, "x": rng.normal(size=n)})
    b.append({"ts": ts + rng.uniform(-0.2, 0.2, size=n),
              "y": rng.normal(size=n)})
    a.flush()
    b.flush()
    return bd


# every family shape the compiler claims; parity must be *bitwise*
_FAMILY = [
    "window(c.p, 32)",
    "window(c.p, 32, 8)",
    "ewindow(c.s, 10, 5)",
    "aggregate(window(c.p, 16), sum(v))",
    "aggregate(window(c.p, 16), avg(v))",
    "aggregate(window(c.p, 16), min(v))",
    "aggregate(window(c.p, 16), max(v))",
    "aggregate(window(c.p, 16), count(*))",
    "aggregate(window(c.p, 32, 8), max(w))",
    "aggregate(ewindow(c.s, 10, 5), sum(x))",
    "join(ewindow(c.s, 20, 10), ewindow(c.s, 20, 10), on=ts, tol=0.5)",
    "join(ewindow(c.a, 20, 10), ewindow(c.b, 20, 10),"
    " on=ts, tol=0.25)",
]


def _run(bd, query, backend, monkeypatch):
    monkeypatch.setenv(qc.BACKEND_ENV, backend)
    return bd.query(f"bdstream({query})").value


def _assert_identical(ref, got, query):
    assert type(ref) is type(got), query
    r_cols = dict(getattr(ref, "columns", None) or ref.attrs)
    g_cols = dict(getattr(got, "columns", None) or got.attrs)
    assert list(r_cols) == list(g_cols), f"column order: {query}"
    for k in r_cols:
        rv, gv = np.asarray(r_cols[k]), np.asarray(g_cols[k])
        assert rv.dtype == gv.dtype, f"{query} [{k}]"
        np.testing.assert_array_equal(rv, gv, err_msg=f"{query} [{k}]")


@pytest.mark.parametrize("query", _FAMILY)
def test_jit_bitwise_parity_per_op(query, monkeypatch):
    pytest.importorskip("jax")
    from repro.stream import shim
    rng = np.random.default_rng(7)
    bd = _deploy(rng)
    before = dict(shim.JOIN_STATS)
    ref = _run(bd, query, "interpreter", monkeypatch)
    mid = dict(shim.JOIN_STATS)
    got = _run(bd, query, "jit", monkeypatch)
    after = dict(shim.JOIN_STATS)
    _assert_identical(ref, got, query)
    st = qc.stats()
    assert st["fallbacks"] == 0, st
    assert st["executions"] >= 1
    # the jit run moves JOIN_STATS exactly as the interpreter run did
    for k in before:
        assert after[k] - mid[k] == mid[k] - before[k], (k, query)


def test_plan_cache_hits_on_second_execution(monkeypatch):
    pytest.importorskip("jax")
    bd = _deploy(np.random.default_rng(8))
    monkeypatch.setenv(qc.BACKEND_ENV, "jit")
    bd.query("bdstream(window(c.p, 32))")
    st = qc.stats()
    assert st["compiles"] == 1 and st["cache_hits"] == 0
    bd.query("bdstream(window(c.p, 32))")
    bd.query("bdstream(window(c.p,   32))")   # normalized: same plan
    st = qc.stats()
    assert st["compiles"] == 1 and st["cache_hits"] == 2


def test_out_of_family_ops_stay_interpreted(monkeypatch):
    bd = _deploy(np.random.default_rng(9))
    monkeypatch.setenv(qc.BACKEND_ENV, "jit")
    bd.query("bdstream(snapshot(c.p))")
    st = qc.stats()
    assert st["interpreted"] == 1
    assert st["fallbacks"] == 0 and st["compiles"] == 0


def test_error_strings_match_interpreter(monkeypatch):
    pytest.importorskip("jax")
    bd = default_deployment()
    bd.register_stream("streamstore0", "c.empty", ("v",), capacity=64)
    msgs = {}
    for backend in ("interpreter", "jit"):
        monkeypatch.setenv(qc.BACKEND_ENV, backend)
        # the executor wraps the StreamException; the *full* wrapped
        # string must match, so the underlying messages are identical
        with pytest.raises(Exception) as exc:
            bd.query("bdstream(window(c.empty, 16))")
        msgs[backend] = str(exc.value)
    assert "no complete window of size 16" in msgs["interpreter"]
    assert msgs["interpreter"] == msgs["jit"]


def test_non_finite_join_keys_fall_back_with_reason(monkeypatch):
    """A compiled join whose *data* defeats it (NaN keys break the
    sorted-search lowering) must fall back to the interpreter and count
    the reason — the jit-parity lane alarms on unexpected fallbacks."""
    pytest.importorskip("jax")
    bd = default_deployment()
    s = bd.register_stream("streamstore0", "c.nan", ("t", "v"),
                           capacity=64)
    t = np.arange(16.0)
    t[3] = np.nan
    s.append({"t": t, "v": np.arange(16.0)})
    q = "bdstream(join(window(c.nan, 16), window(c.nan, 16)," \
        " on=t, tol=0.5))"
    monkeypatch.setenv(qc.BACKEND_ENV, "interpreter")
    ref = bd.query(q).value
    monkeypatch.setenv(qc.BACKEND_ENV, "jit")
    got = bd.query(q).value
    _assert_identical(ref, got, q)        # interpreter served both
    st = qc.stats()
    assert st["fallbacks"] == 1
    assert st["fallback_reasons"] == {"non-finite join keys": 1}


def test_compiled_path_does_not_flip_global_x64(monkeypatch):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    bd = _deploy(np.random.default_rng(11))
    ambient = jnp.asarray(np.zeros(1)).dtype
    monkeypatch.setenv(qc.BACKEND_ENV, "jit")
    out = bd.query("bdstream(window(c.p, 32))").value
    # outputs land in the ambient default dtype and the global default
    # is untouched — the f64 math happened under a *scoped* enable_x64
    assert np.asarray(out.attrs["v"]).dtype == ambient
    assert jnp.asarray(np.zeros(1)).dtype == ambient
    assert not jax.config.jax_enable_x64


def test_backend_env_validation_and_default(monkeypatch):
    monkeypatch.delenv(qc.BACKEND_ENV, raising=False)
    assert qc.backend() == "interpreter"
    monkeypatch.setenv(qc.BACKEND_ENV, "jit")
    assert qc.backend() == "jit"

