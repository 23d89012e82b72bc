"""The batch polystore cells at CPU size: cohort casts and waveform
filters agree with the plain reference, and each fault the cells can
have, and the bfloat16 control, fail the check."""
import pytest

from chipbench.tests import faults
from chipbench.tests.small import run

CELLS = ["batch-cast", "batch-array"]


@pytest.mark.parametrize("name", CELLS)
def test_batch_cell_is_correct(monkeypatch, name):
    line = run(name, monkeypatch=monkeypatch)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["query_p95_ms"]["value"] > 0


@pytest.mark.parametrize("fault", ["altered", "halved", "stale"])
@pytest.mark.parametrize("name", CELLS)
def test_batch_cell_catches_fault(monkeypatch, name, fault):
    from repro.core.api import BigDawg

    query = BigDawg.query
    stale = faults.Stale()

    def broken(self, bql, training=False):
        resp = query(self, bql, training)
        if fault == "stale":
            resp.value = stale("q", resp.value)
        else:
            resp.value = faults.map_value(resp.value,
                                          getattr(faults, fault))
        return resp

    monkeypatch.setattr(BigDawg, "query", broken)
    line = run(name, monkeypatch=monkeypatch)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_batch_control_fails_its_limits(monkeypatch, name):
    import jax
    from chipbench import control, harness
    from chipbench.tests.small import small

    c, cfg, traffic = small(name)
    [(_, prog, ctrl)] = control.readings(
        c, [11], 1.0, 1, jax.devices()[:1], lambda *a: None, cfg=cfg,
        traffic=traffic)
    limits = harness.load("limits", name)
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert any(ctrl[k] > limits[k] for k in limits), ctrl


@pytest.mark.parametrize("control", [False, True])
def test_wave_answers_match_whole_array_numpy(control):
    import numpy as np
    from chipbench import answers
    from chipbench.generators.batch import wave_answers

    sig = np.random.default_rng(3).standard_normal((13, 999)) \
        .astype(np.float32)
    params = [{"signal_above": x} for x in (0.0, 0.5, 2.5, 9.0)]
    got = wave_answers(sig, params, control, workers=3)
    full = answers.bf16(sig).astype(np.float32) if control else sig
    for p in params:
        v = full[full > np.float32(p["signal_above"])].astype(np.float64)
        w = got[p["signal_above"]]
        assert w["count"] == v.size
        if v.size:
            assert w["max"] == v.max()
            assert w["avg"] == pytest.approx(v.mean(), rel=1e-12)
            assert w["scale"] == pytest.approx(np.abs(v).mean(), rel=1e-12)
