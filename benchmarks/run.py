"""Benchmark harness (deliverable (d)): one module per paper table/figure
plus migration matrix, kernels, planner/monitor, and the dry-run roofline
reader.  Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` also
writes a machine-readable report (uploaded as the CI bench-smoke
artifact, named ``BENCH_<sha>.json`` there — the bench trajectory).

  PYTHONPATH=src python -m benchmarks.run [--only fig5,fig6,...] [--json out.json]

Perf-regression gate: ``--compare BASELINE.json --tolerance 0.25`` diffs
the current run's per-row **medians** (collect several with
``--samples N``; rows repeating a name within one report are pooled)
against a committed baseline report and exits non-zero when any common
row's median exceeds ``baseline * (1 + tolerance)`` — so speedups and
regressions stop being invisible in CI.  ``--write-baseline PATH``
refreshes the committed baseline from the current run.

Row kinds: most rows are wall-clock (``us_per_call``, smaller is
better).  A suite may mark a row ``kind="ratio"`` (4th tuple element):
its value is a self-normalizing bigger-is-better ratio (e.g. concurrent
vs serial ingest throughput measured in the same pass), so the gate
compares ratios directly and stays machine-independent — runner drift
cannot fire it and cannot hide behind a baseline refresh either.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import traceback
from typing import Any, Dict, List, Tuple

SUITES = ("fig5", "fig6", "migration", "kernels", "planner", "stream",
          "serve", "ml", "roofline")


def _run_suite(name: str, runs: int) -> List[Tuple[str, float, str]]:
    if name == "fig5":
        from benchmarks import paper_fig5
        return paper_fig5.run(runs=runs)
    if name == "fig6":
        from benchmarks import paper_fig6
        return paper_fig6.run(runs=runs)
    if name == "migration":
        from benchmarks import migration_matrix
        return migration_matrix.run()
    if name == "kernels":
        from benchmarks import kernel_bench
        return kernel_bench.run()
    if name == "planner":
        from benchmarks import planner_monitor
        return planner_monitor.run()
    if name == "stream":
        from benchmarks import stream_bench
        return stream_bench.run()
    if name == "serve":
        from benchmarks import serve_bench
        return serve_bench.run()
    if name == "ml":
        from benchmarks import ml_bench
        return ml_bench.run()
    if name == "roofline":
        from benchmarks import roofline
        return roofline.run()
    raise ValueError(f"unknown suite {name!r}")


def _row_pools(report: Dict[str, Any]
               ) -> Dict[Tuple[str, str], List[float]]:
    """(suite, row name) -> every us_per_call occurrence in the report
    (multiple ``--samples`` passes repeat row names)."""
    pools: Dict[Tuple[str, str], List[float]] = {}
    for suite, rows in report.get("suites", {}).items():
        for row in rows:
            pools.setdefault((suite, row["name"]), []).append(
                float(row["us_per_call"]))
    return pools


def report_medians(report: Dict[str, Any]) -> Dict[Tuple[str, str], float]:
    """(suite, row name) -> median us_per_call over every occurrence."""
    return {k: statistics.median(v)
            for k, v in _row_pools(report).items()}


def report_kinds(report: Dict[str, Any]) -> Dict[Tuple[str, str], str]:
    """(suite, row name) -> row kind for rows that declare one ("ratio"
    or "time"); rows without a kind field are omitted, so a report from
    before the field existed cannot demote a known ratio row."""
    kinds: Dict[Tuple[str, str], str] = {}
    for suite, rows in report.get("suites", {}).items():
        for row in rows:
            if "kind" in row:
                kinds[(suite, row["name"])] = row["kind"]
    return kinds


def compare_reports(baseline: Dict[str, Any], current: Dict[str, Any],
                    tolerance: float = 0.25) -> Dict[str, Any]:
    """Diff two ``--json`` reports by per-row median us_per_call.

    A row *regresses* when its current **median** exceeds the baseline
    median by more than ``tolerance`` (relative) AND its best (minimum)
    sample does too: a genuine code regression elevates every sample,
    while scheduler noise on micro-rows usually leaves at least one
    sample near baseline — so one lucky sample vetoes a false alarm but
    cannot hide a real slowdown.  Rows faster by the same margin are
    reported as improvements.  Only rows present in both reports are
    compared — renamed or new rows can't fail the gate, but they are
    listed so a silently vanished benchmark is visible.

    ``kind="ratio"`` rows invert the direction: their value is a
    bigger-is-better self-normalized ratio, so a row regresses when its
    current median falls below ``baseline * (1 - tolerance)`` AND its
    best (maximum) sample does too."""
    base = report_medians(baseline)
    cur = report_medians(current)
    cur_pools = _row_pools(current)
    # the current report's kind wins (a row may change kind in the PR
    # that converts it); baseline-only kinds cover the transition run
    kinds = {**report_kinds(baseline), **report_kinds(current)}
    rows, regressions, improvements = [], [], []
    for key in sorted(base.keys() & cur.keys()):
        b, c = base[key], cur[key]
        ratio = c / b if b > 0 else float("inf")
        kind = kinds.get(key, "time")
        if kind == "ratio":
            cutoff = b * (1.0 - tolerance)
            regressed = c < cutoff and max(cur_pools[key]) < cutoff
            improved = c > b * (1.0 + tolerance)
        else:
            cutoff = b * (1.0 + tolerance)
            regressed = c > cutoff and min(cur_pools[key]) > cutoff
            improved = c < b * (1.0 - tolerance)
        name = f"{key[0]}/{key[1]}" if not key[1].startswith(key[0]) \
            else key[1]
        rows.append({"suite": key[0], "name": key[1], "kind": kind,
                     "baseline_us": round(b, 3), "current_us": round(c, 3),
                     "ratio": round(ratio, 4), "regressed": regressed})
        if regressed:
            regressions.append(name)
        elif improved:
            improvements.append(name)
    return {"tolerance": tolerance, "rows": rows,
            "regressions": regressions, "improvements": improvements,
            "only_in_baseline": sorted(
                f"{s}/{n}" for s, n in base.keys() - cur.keys()),
            "only_in_current": sorted(
                f"{s}/{n}" for s, n in cur.keys() - base.keys())}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--runs", type=int, default=50,
                    help="repetitions for fig5/fig6 (paper uses 50)")
    ap.add_argument("--samples", type=int, default=1,
                    help="full passes over the selected suites; per-row "
                         "medians pool across passes (use >1 with "
                         "--compare for stable medians)")
    ap.add_argument("--json", type=str, default=None,
                    help="also write results as JSON to this path")
    ap.add_argument("--compare", type=str, default=None,
                    help="baseline report JSON to diff medians against; "
                         "exits non-zero on any regression")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="relative regression tolerance for --compare "
                         "(0.25 = fail rows >25%% over baseline)")
    ap.add_argument("--write-baseline", type=str, default=None,
                    help="write this run's report as a fresh baseline")
    args = ap.parse_args()
    selected = args.only.split(",") if args.only else list(SUITES)
    from repro.stream.compile import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    report: Dict[str, Any] = {"suites": {}, "meta": {}, "failures": []}
    for _ in range(max(1, args.samples)):
        for name in selected:
            if name not in SUITES:
                print(f"unknown suite {name}", file=sys.stderr)
                continue
            try:
                rows = _run_suite(name, args.runs)
                if name == "stream":
                    # shard/engine config rides along so BENCH_*.json
                    # trajectories stay comparable across shard configs
                    from benchmarks import stream_bench
                    report["meta"]["stream"] = dict(stream_bench.LAST_META)
                if name == "serve":
                    from benchmarks import serve_bench
                    report["meta"]["serve"] = dict(serve_bench.LAST_META)
                if name == "ml":
                    from benchmarks import ml_bench
                    report["meta"]["ml"] = dict(ml_bench.LAST_META)
                for row in rows:
                    row_name, us, derived = row[0], row[1], row[2]
                    kind = row[3] if len(row) > 3 else "time"
                    report["suites"].setdefault(name, []).append(
                        {"name": row_name, "us_per_call": us,
                         "derived": derived, "kind": kind})
                    value = f"{us:.3f}" if kind == "ratio" else f"{us:.1f}"
                    print(f"{row_name},{value},{derived}")
            except Exception:                             # noqa: BLE001
                report["failures"].append(
                    {"suite": name, "traceback": traceback.format_exc()})
                traceback.print_exc()

    # the unified metrics registry accumulated over every suite rides
    # along (one scrape per bench run), so a BENCH_*.json also carries
    # the observability view of what the benchmarks actually did
    from repro.obs import metrics as obs_metrics
    report["meta"]["obs"] = obs_metrics.snapshot()

    comparison = None
    if args.compare:
        with open(args.compare) as fh:
            baseline = json.load(fh)
        comparison = compare_reports(baseline, report,
                                     tolerance=args.tolerance)
        report["compare"] = dict(comparison, baseline=args.compare)
        for row in comparison["rows"]:
            flag = "REGRESSED" if row["regressed"] else "ok"
            print(f"compare,{row['suite']}/{row['name']},"
                  f"{row['ratio']:.2f}x,{flag}", file=sys.stderr)
        if comparison["regressions"]:
            print(f"PERF REGRESSION (> {args.tolerance:.0%} over "
                  f"{args.compare}): "
                  + ", ".join(comparison["regressions"]),
                  file=sys.stderr)
        else:
            print(f"perf gate OK: {len(comparison['rows'])} rows within "
                  f"{args.tolerance:.0%} of {args.compare}"
                  + (f" (improved: "
                     f"{', '.join(comparison['improvements'])})"
                     if comparison["improvements"] else ""),
                  file=sys.stderr)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1)
    if args.write_baseline:
        with open(args.write_baseline, "w") as fh:
            json.dump(report, fh, indent=1)
    if report["failures"]:
        sys.exit(1)
    if comparison is not None and comparison["regressions"]:
        sys.exit(2)


if __name__ == "__main__":
    main()
