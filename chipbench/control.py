"""Readings that set the limits of a cell's correctness check.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,... \
        --seconds <s> [--control-seeds 3]

In one process, for each seed: the cell's set-up, a measured window of
``--seconds`` at the cell's own load, and the comparison with the plain
reference, as a run makes it (the program's readings).  For the first
``--control-seeds`` seeds also the control's readings: the same
comparison with the reference, computed in the next lower precision,
in the program's place.  A limit lies above the largest program reading
and below the smallest control reading (PERF.md gives both).  One JSON
line per seed.  TPU only, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cell, seeds, seconds, control_seeds, devices, log, cfg=None,
             traffic=None):
    """[(seed, program readings, control readings or None)]."""
    from chipbench import harness
    cfg = cfg or harness.load("configs", cell["config"])
    traffic = traffic or harness.load("traffic", cell["traffic"])
    drv = harness.generator(traffic["generator"])
    compiles = harness.Compiles()
    out = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        state = drv.setup(cfg, traffic, seed, devices, log)
        probe = harness.Probe(False, 0.0, compiles)
        res = drv.run(state, seconds, probe, log)
        probe.end()
        prog = drv.check(state, res, log)
        ctrl = drv.control(state, res, log) if i < control_seeds else None
        log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
        out.append((seed, prog, ctrl))
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl}),
              flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    from chipbench import harness
    cell = harness.cell(args.workload)

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    devices = harness.tpu_devices(cell, log)
    if devices is None:
        return 3
    readings(cell, [int(s) for s in args.seeds.split(",")], args.seconds,
             args.control_seeds, devices, log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
