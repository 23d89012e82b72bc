"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(or ``python3 -m chipbench.run ...``) from the root of a checkout.  The
run sets up the cell's deployment and load from ``--seed``, warms every
shape the load uses, measures for ``--seconds``, frees the program's
state, checks a sample of the answers against the plain reference and
prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, the metrics (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), the device, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit.  The same numbers are the last lines on standard
error.

It runs on a TPU only: with any other platform, or fewer chips than
the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from chipbench import harness

    cells = {w["name"]: w for w in harness.benchmark()["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r} (have {sorted(cells)})")
        return 2
    devices = harness.tpu_devices(cells[args.workload], log)
    if devices is None:
        return 3
    line = harness.run_cell(cells[args.workload], args.seed, args.seconds,
                            bool(args.trace), devices, T_START, log)
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
