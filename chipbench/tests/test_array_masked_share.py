"""The array island's reader ``array.masked_share`` on synthetic traced
windows: the share of aggregates that reduced over a built mask, and
None where neither aggregate counter moved (a program without them)."""
import pytest

from chipbench import harness


def ctx(work, registry=None):
    return {"spans": [], "work": work, "registry": registry or {}}


@pytest.mark.parametrize("window,want", [
    (ctx({"queries": 4}, {"repro_array_fused_aggregates_total": 3,
                          "repro_array_masked_aggregates_total": 1}), 25.0),
    (ctx({"queries": 4}, {"repro_array_fused_aggregates_total": 4}), 0.0),
    (ctx({"queries": 2}, {"repro_array_masked_aggregates_total": 2}), 100.0),
    (ctx({}, {"repro_array_fused_aggregates_total": 0}), None),
    (ctx({"ticks": 3, "queries": 3},
         {"repro_stream_tick_seconds": (3, 0.3)}), None),
], ids=["mixed", "all-fused", "all-masked", "no-work", "nothing-to-read"])
def test_masked_share(window, want):
    got = harness.reader("array.masked_share").read(window)
    assert got == (None if want is None else pytest.approx(want))
