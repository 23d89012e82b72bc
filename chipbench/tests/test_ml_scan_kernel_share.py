"""The ml island's reader ``ml.scan_kernel_share`` on synthetic traced
windows: the share of scored Mamba layers whose scan ran as the kernel,
and None where neither scan counter moved (a program without them)."""
import pytest

from chipbench import harness


def ctx(registry):
    return {"spans": [], "work": {"waves": 2}, "registry": registry}


@pytest.mark.parametrize("window,want", [
    (ctx({"repro_ml_scan_kernel_total": 52}), 100.0),
    (ctx({"repro_ml_scan_kernel_total": 26,
          "repro_ml_scan_reference_total": 78}), 25.0),
    (ctx({"repro_ml_scan_reference_total": 52}), 0.0),
    (ctx({"repro_ml_tokens_scored_total{arch=jamba2-3b}": 4096}), None),
], ids=["all-kernel", "mixed", "all-reference", "no-counts"])
def test_scan_kernel_share(window, want):
    got = harness.reader("ml.scan_kernel_share").read(window)
    assert got == (None if want is None else pytest.approx(want))
