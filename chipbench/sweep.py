"""Bed-count sweep of the standing-query cell: the highest ward size the
program sustains.

    python3 chipbench/sweep.py --beds 64,128,256,512 --seconds 10 [--seed n]

For each bed count, in one process: the ``icu-standing`` cell's set-up
and a measured window at that count.  It prints one JSON line per
count: the p95 of event-to-result latency, the generator's lateness at
the window's start and end (a backlog that grows shows as lateness that
grows), and the correctness readings.  "Sustains" means p95 at or under
the batch period with no growing backlog; the cell runs at four fifths
of the highest such count, rounded down to a multiple of the shard
count.  TPU only.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--beds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--workload", default="icu-standing")
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    from chipbench import harness
    cell = harness.cell(args.workload)
    cfg = harness.load("configs", cell["config"])
    traffic = harness.load("traffic", cell["traffic"])

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    devices = harness.tpu_devices(cell, log)
    if devices is None:
        return 3
    drv = harness.generator(traffic["generator"])
    compiles = harness.Compiles()
    period = cfg["batch_period_s"]
    for beds in [int(b) for b in args.beds.split(",")]:
        st = drv.setup(cfg, dict(traffic, beds=beds), args.seed,
                       devices, log)
        probe = harness.Probe(False, 0.0, compiles)
        gc.collect()
        gc.freeze()                     # as a run of the cell does
        res = drv.run(st, args.seconds, probe, log)
        probe.end()
        gc.unfreeze()
        late = res["late"]
        p95 = res["e2e"].get("event_to_result_p95_ms")
        readings = drv.check(st, res, log)
        k = max(1, len(late) // 5)
        out = {"beds": beds, "p95_ms": p95,
               "late_ms_first": 1e3 * sum(late[:k]) / k,
               "late_ms_last": 1e3 * sum(late[-k:]) / k,
               "failed": res["failed"], "readings": readings,
               "compiles_in_window": probe.compiles_in_window}
        out["sustained"] = (p95 is not None and p95 <= 1e3 * period
                            and out["late_ms_last"] < 1e3 * period / 2
                            and res["failed"] == 0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
