"""The array island's fused filter+aggregate: a filter stays pending as
(lhs, op, value) conditions, and aggregate evaluates them inside one
jitted reduction.  Every case compares it with the mask path (the same
aggregate over the mask built first) and the mask with numpy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datamodel as dm
from repro.core.shims import _afl_condition
from repro.obs import metrics

NP_OPS = {">=": np.greater_equal, "<=": np.less_equal, "!=": np.not_equal,
          "=": np.equal, ">": np.greater, "<": np.less}
AGGS = ("count", "sum", "avg", "min", "max")

# (conditions, base valid?) per case
CASES = {f"signal{op}": ([("signal", op, 0.5)], False) for op in dm.OPS}
CASES.update({
    "level<=int": ([("level", "<=", 3)], False),
    "tick<int": ([("tick", "<", 20)], False),
    "lead>=int": ([("lead", ">=", 2)], False),
    "tick<float": ([("tick", "<", 20.5)], False),
    "chained-attr-dim": ([("signal", ">", -0.25), ("tick", "!=", 3)], False),
    "chained-two-attrs": ([("signal", ">", 0.0), ("level", "<", 4)], False),
    "over-valid": ([("signal", ">", 0.0)], True),
    "empty": ([("signal", ">", 100.0)], False),
})


def wave(valid: bool) -> dm.ArrayObject:
    rng = np.random.default_rng(7)
    # quarter steps, so '=' and '!=' select some cells and miss others
    sig = (rng.integers(-8, 9, (6, 50)) / 4).astype(np.float32)
    level = rng.integers(0, 8, (6, 50)).astype(np.int32)
    return dm.ArrayObject(
        {"signal": jnp.asarray(sig), "level": jnp.asarray(level)},
        ("lead", "tick"),
        jnp.asarray(rng.random((6, 50)) < 0.7) if valid else None)


def filtered(case: str) -> dm.ArrayObject:
    conds, valid = CASES[case]
    arr = wave(valid)
    for c in conds:
        arr = arr.filter(*c)
    return arr


def numpy_mask(arr: dm.ArrayObject, conds) -> np.ndarray:
    m = np.ones(arr.shape, bool) if arr.valid is None \
        else np.asarray(arr.valid)
    for lhs, op, value in conds:
        if lhs in arr.attrs:
            field = np.asarray(arr.attrs[lhs])
        else:
            axis = arr.dim_names.index(lhs)
            field = np.indices(arr.shape)[axis]
        m = m & NP_OPS[op](field, value)
    return m


def counts():
    return tuple(metrics.counter(f"repro_array_{k}_aggregates_total").value
                 for k in ("fused", "masked"))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("agg", AGGS)
def test_fused_aggregate_equals_mask_path(agg, case):
    arr = filtered(case)
    masked = dm.ArrayObject(arr.attrs, arr.dim_names, arr.mask())
    f0, m0 = counts()
    got = np.asarray(arr.aggregate(agg, "signal").attrs[f"{agg}_signal"])
    f1, m1 = counts()
    want = np.asarray(masked.aggregate(agg, "signal")
                      .attrs[f"{agg}_signal"])
    f2, m2 = counts()
    assert (f1 - f0, m1 - m0, f2 - f1, m2 - m1) == (1, 0, 0, 1)
    assert got.dtype == want.dtype and got.shape == want.shape == (1,)
    if agg in ("count", "min", "max"):
        assert np.array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    if case == "empty":
        assert got[0] == {"count": 0, "sum": 0, "avg": 0,
                          "min": np.inf, "max": -np.inf}[agg]


@pytest.mark.parametrize("case", sorted(CASES))
def test_mask_applies_pending_conditions(case):
    arr = filtered(case)
    assert arr.conds == tuple(CASES[case][0])
    base = wave(CASES[case][1])
    assert np.array_equal(np.asarray(arr.mask()),
                          numpy_mask(base, CASES[case][0]))
    # the operators that need the mask build it, then drop the conditions
    flat = arr.redimension((300,), ("i",))
    assert flat.conds == () and np.array_equal(
        np.asarray(flat.valid), np.asarray(arr.mask()).reshape(-1))
    assert np.array_equal(np.asarray(arr.project(["level"]).valid),
                          np.asarray(arr.mask()))


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_thresholds_compare_in_the_attributes_dtype(x64):
    # float32(0.1) > 0.1 in float64, not in float32; the reference
    # compares float32 against np.float32(x)
    sig = np.asarray([[0.1, 0.2, 0.05, 0.1]], np.float32)
    arr = dm.ArrayObject({"signal": jnp.asarray(sig)}, ("lead", "tick"))
    with jax.enable_x64(x64):
        got = arr.filter("signal", ">", 0.1).aggregate("count", "signal")
        assert int(got.attrs["count_signal"][0]) == \
            int((sig > np.float32(0.1)).sum()) == 1


def test_thresholds_share_one_program_per_aggregate():
    sig = jnp.asarray(np.linspace(-1, 2, 7 * 33, dtype=np.float32)
                      .reshape(7, 33))
    arr = dm.ArrayObject({"signal": sig}, ("lead", "tick"))
    before = dm._filter_reduce._cache_size()
    for x in np.linspace(0, 1.8, 16):
        for agg in ("count", "avg", "max"):
            arr.filter("signal", ">", float(x)).aggregate(agg, "signal")
    assert dm._filter_reduce._cache_size() - before == 3


def test_unfiltered_aggregate_moves_no_counter():
    # the stream windows' path: no filter, no valid
    arr = dm.ArrayObject({"a": jnp.arange(12.0)}, ("tick",))
    before = counts()
    for agg in AGGS:
        arr.aggregate(agg, "a")
    assert counts() == before


@pytest.mark.parametrize("cond,want", [
    ("signal > 0.5", ("signal", ">", 0.5)),
    ("dim1>=150", ("dim1", ">=", 150)),
    ("lead != 2", ("lead", "!=", 2))])
def test_afl_condition_parses_to_data(cond, want):
    assert _afl_condition(cond) == want


def test_filter_refuses_unknown_names():
    with pytest.raises(ValueError, match="unknown attr/dim"):
        wave(False).filter("nope", ">", 1)
