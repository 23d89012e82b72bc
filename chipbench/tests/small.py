"""Cells at sizes a CPU test holds: the same generators, references and
checks as a chip run, with the load and the data cut down."""
import os

from chipbench import harness


def small(name):
    """(cell, config, traffic) of ``name``, cut to CPU size."""
    c = harness.cell(name)
    cfg = harness.load("configs", c["config"])
    traffic = harness.load("traffic", c["traffic"])
    if traffic["generator"] == "standing":
        traffic["beds"] = 8
        traffic["check_ticks"] = 6
    else:
        cfg.update(patients=100, orders=2000, notes=10, bed_days=2,
                   samples_per_day=2500)
    return c, cfg, traffic


def run(name, seed=2**33 + 5, seconds=1.0, monkeypatch=None,
        limits=None):
    """One run of ``name`` at CPU size, past the look for a chip."""
    import jax
    c, cfg, traffic = small(name)
    for k, v in cfg.get("env", {}).items():
        monkeypatch.setenv(k, v)
    return harness.run_cell(c, seed, seconds, False, jax.devices()[:1],
                            0.0, lambda *a: None, cfg=cfg, traffic=traffic,
                            limits=limits)


os.environ.setdefault("JAX_PLATFORMS", "cpu")
