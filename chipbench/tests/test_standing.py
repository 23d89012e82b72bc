"""The standing-query cell at CPU size: its generator, reference and
check pass on the program, and fail on each fault the cell can have."""
import pytest

from chipbench.tests import faults
from chipbench.tests.small import run


def test_standing_cell_is_correct(monkeypatch):
    line = run("icu-standing", monkeypatch=monkeypatch)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["event_to_result_p95_ms"]["value"] > 0
    assert set(line["checks"]) == {"missing", "mismatch", "value_err"}


@pytest.mark.parametrize("fault", ["altered", "halved", "stale", "dropped"])
def test_standing_cell_catches_fault(monkeypatch, fault):
    from repro.serve.frontdoor import Subscription

    push = Subscription._push
    stale = faults.Stale()
    count = [0]

    def broken(self, tick, value):
        count[0] += 1
        if fault == "dropped":
            if count[0] % 7 == 0:
                return None
        elif fault == "stale":
            value = stale(self.sub_id, value)
        else:
            value = faults.map_value(value, getattr(faults, fault))
        return push(self, tick, value)

    monkeypatch.setattr(Subscription, "_push", broken)
    line = run("icu-standing", seconds=2.0, monkeypatch=monkeypatch)
    assert not line["correct"], line["checks"]


def test_standing_control_fails_its_limits(monkeypatch):
    from chipbench import control, harness
    from chipbench.tests.small import small
    import jax

    c, cfg, traffic = small("icu-standing")
    for k, v in cfg["env"].items():
        monkeypatch.setenv(k, v)
    [(_, prog, ctrl)] = control.readings(
        c, [7], 1.0, 1, jax.devices()[:1], lambda *a: None, cfg=cfg,
        traffic=traffic)
    limits = harness.load("limits", "icu-standing")
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert any(ctrl[k] > limits[k] for k in limits), ctrl
