"""Polystore core tests: BQL parsing, island queries (the paper's §VI
examples), planner training/lean modes, monitor matching, migrator routes,
catalog queries — the paper's behaviour as executable assertions."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bql, datamodel as dm, signatures
from repro.core.api import default_deployment
from repro.core.migrator import MigrationParams
from repro.data.mimic import load_mimic_demo


@pytest.fixture(scope="module")
def bd():
    bd = default_deployment()
    load_mimic_demo(bd, num_patients=64, num_orders=256, wave_len=512,
                    num_logs=32)
    return bd


# -- BQL parser ----------------------------------------------------------------
def test_parse_simple_island():
    root = bql.parse("bdrel(select * from t limit 4)")
    assert root.island == "relational"
    assert root.query == "select * from t limit 4"
    assert root.casts == []


def test_parse_nested_cast():
    q = ("bdarray(scan(bdcast(bdrel(select a from t), obj,"
         " '<a:int32>[i=0:*,10,0]', array)))")
    root = bql.parse(q)
    assert root.island == "array"
    assert "obj" in root.query and "bdcast" not in root.query
    assert len(root.casts) == 1
    cast = root.casts[0]
    assert cast.dest_name == "obj"
    assert cast.dest_island == "array"
    assert cast.child.island == "relational"


def test_parse_double_nested_cast():
    q = ("bdrel(select * from bdcast(bdarray(filter(bdcast(bdrel("
         "select a from t), x, 's1', array), dim1>0)), y, 's2',"
         " relational) limit 2)")
    root = bql.parse(q)
    assert len(root.casts) == 1
    inner = root.casts[0].child
    assert inner.island == "array"
    assert len(inner.casts) == 1
    assert inner.casts[0].child.island == "relational"


def test_parse_catalog():
    root = bql.parse("bdcatalog(select * from engines)")
    assert isinstance(root, bql.CatalogQueryNode)


def test_parse_rejects_garbage():
    with pytest.raises(ValueError):
        bql.parse("select * from t")
    with pytest.raises(ValueError):
        bql.parse("bdcast(bdrel(select 1), a, b)")


# -- island queries (paper examples) ---------------------------------------------
def test_relational_island_limit(bd):
    r = bd.query("bdrel(select * from mimic2v26.d_patients limit 4)")
    assert r.value.num_rows == 4


def test_relational_island_filter_agg(bd):
    r = bd.query("bdrel(select count(*) from mimic2v26.d_patients"
                 " where sex = 1)")
    cnt = int(np.asarray(next(iter(r.value.columns.values())))[0])
    full = bd.engines["hoststore0"].get("mimic2v26.d_patients")
    want = int(np.asarray(full.columns["sex"]).sum())
    assert cnt == want


def test_relational_group_by(bd):
    r = bd.query("bdrel(select sex, avg(dob_year) from"
                 " mimic2v26.d_patients group by sex)")
    assert r.value.num_rows == 2


def test_array_island_filter(bd):
    r = bd.query("bdarray(filter(myarray, dim1>150))")
    assert int(r.value.mask().sum()) == 256 - 151


def test_array_island_aggregate_over_filter(bd):
    # a comparison inside a nested operator must not unbalance the
    # argument split (">" is not a bracket outside a quoted schema)
    r = bd.query("bdarray(aggregate(filter(mimic2v26.waveform,"
                 " signal>1.0), count(signal)))")
    full = np.asarray(bd.engines["densehbm0"].get(
        "mimic2v26.waveform").attrs["signal"])
    assert int(np.asarray(r.value.attrs["count_signal"])[0]) == \
        int((full > 1.0).sum())


def test_array_island_aggregate(bd):
    r = bd.query("bdarray(aggregate(mimic2v26.waveform, avg(signal)))")
    got = float(np.asarray(next(iter(r.value.attrs.values())))[0])
    full = bd.engines["densehbm0"].get("mimic2v26.waveform")
    want = float(jnp.mean(full.attrs["signal"]))
    assert abs(got - want) < 1e-6


def test_text_island_range(bd):
    r = bd.query("bdtext({ 'op' : 'range', 'table' : 'mimic_logs',"
                 " 'range' : { 'start' : ['r_0001','',''],"
                 " 'end' : ['r_0015','',''] } })")
    assert len(r.value) == 15


def test_inter_island_cast_rel_to_array(bd):
    q = ("bdarray(scan(bdcast(bdrel(select poe_id, subject_id from"
         " mimic2v26.poe_order), poe_order_copy,"
         " '<subject_id:int32>[poe_id=0:*,10000000,0]', array)))")
    r = bd.query(q)
    assert "subject_id" in r.value.attrs
    assert r.value.dim_names == ("poe_id",)
    stage_names = [s for s, _ in r.stages]
    assert any("Migration" in s for s in stage_names)


def test_catalog_query(bd):
    r = bd.query("bdcatalog(select name from engines)")
    names = {row["name"] for row in r.value}
    assert {"hoststore0", "densehbm0", "kvstore0"} <= names


# -- planner / monitor ------------------------------------------------------------
def test_training_mode_explores_and_lean_follows(bd):
    q = ("bdarray(scan(bdcast(bdrel(select poe_id, dose from"
         " mimic2v26.poe_order), d_copy,"
         " '<dose:double>[poe_id=0:*,1000,0]', array)))")
    r_train = bd.query(q, training=True)
    assert r_train.plans_considered > 1
    r_lean = bd.query(q, training=False)
    assert r_lean.qep_id == r_train.qep_id     # follows the trained best


def test_monitor_closest_signature(bd):
    s1 = signatures.of_query(bql.parse(
        "bdrel(select * from mimic2v26.d_patients limit 4)"))
    s2 = signatures.of_query(bql.parse(
        "bdrel(select * from mimic2v26.d_patients limit 9)"))
    assert s1.distance(s2) == 0.0              # same structure
    s3 = signatures.of_query(bql.parse("bdarray(filter(myarray, dim1>1))"))
    assert s1.distance(s3) > 1.0
    bd.monitor.add_measurement(s1, "qepX", 0.002)
    got = bd.monitor.get_closest_signature(s2)
    assert got is not None and got.distance(s2) <= s3.distance(s2)


def test_monitor_straggler_detection(bd):
    m = bd.monitor
    for _ in range(8):
        m.observe_engine("fast_a", 0.001)
        m.observe_engine("fast_b", 0.0012)
        m.observe_engine("slow_c", 0.5)
    assert "slow_c" in m.stragglers(factor=3.0)
    assert "fast_a" not in m.stragglers(factor=3.0)


# -- migrator ------------------------------------------------------------------
def test_binary_and_staged_agree(bd):
    src = bd.engines["hoststore0"]
    dst = bd.engines["densehbm0"]
    for method in ("binary", "staged"):
        bd.migrator.migrate(src, "mimic2v26.poe_order", dst,
                            f"poe_{method}", MigrationParams(method=method))
    b = dst.get("poe_binary")
    s = dst.get("poe_staged")
    for field in b.attrs:
        np.testing.assert_allclose(np.asarray(b.attrs[field], np.float64),
                                   np.asarray(s.attrs[field], np.float64),
                                   rtol=1e-12)


def test_quant_migration_bounded_error(bd):
    src = bd.engines["densehbm0"]
    dst = bd.engines["kvstore0"]
    bd.migrator.migrate(src, "mimic2v26.waveform", dst, "wave_q",
                        MigrationParams(method="quant"))
    from repro.kernels.quant_cast import ops as qops
    q = dst.get("wave_q")["signal"]
    orig = src.get("mimic2v26.waveform").attrs["signal"]
    back = qops.dequantize(q["q"], q["scale"], orig.shape)
    err = float(jnp.max(jnp.abs(back - jnp.asarray(orig, jnp.float32))))
    bound = float(jnp.max(jnp.abs(orig))) / 127.0 * 1.01
    assert err <= bound


def test_migration_result_accounting(bd):
    src = bd.engines["hoststore0"]
    dst = bd.engines["hoststore1"]
    res = bd.migrator.migrate(src, "mimic2v26.d_patients", dst,
                              "dp_copy", MigrationParams(method="binary"))
    assert res.rows == 64
    assert res.bytes_moved > 0
    assert res.seconds >= 0


# -- catalog --------------------------------------------------------------------
def test_catalog_persistence_roundtrip(tmp_path, bd):
    path = str(tmp_path / "catalog.json")
    bd.catalog.save(path)
    from repro.core.catalog import Catalog
    loaded = Catalog.load(path)
    assert {e.name for e in loaded.engines.values()} \
        == {e.name for e in bd.catalog.engines.values()}
    assert len(loaded.objects) == len(bd.catalog.objects)
    assert loaded.engines_for_island("array")[0].name == "densehbm0"
