"""Workload atlas sweep — the scenario matrix as a single CLI.

Runs every scenario in ``repro.sim.atlas`` (diurnal, flash crowd,
endpoint blackout, network partition, straggler storm, hot-key drift,
and the three multi-tenant mixes) across a seed set on virtual time and
writes one canonical, fully-sorted JSON report.  The report is the
determinism artifact: CI runs this twice and byte-compares the files.

Gates (computed inside ``run_atlas`` and echoed in the verdict):

  * every per-tenant loss ledger closes in every run;
  * every run analyzes at least one record (no silently-dead scenario).

  PYTHONPATH=src python benchmarks/atlas.py
      [--scenarios a,b] [--seeds 0,1,2] [--report PATH] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache
from repro.sim.atlas import SCENARIOS, report_json, run_atlas

if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scenarios", default=None,
                   help=f"comma-separated subset of {sorted(SCENARIOS)}")
    p.add_argument("--seeds", default="0,1,2",
                   help="comma-separated VirtualClock seeds")
    p.add_argument("--report",
                   default=str(Path(__file__).resolve().parents[1]
                               / "ATLAS_report.json"),
                   help="canonical report artifact (byte-compared in CI)")
    p.add_argument("--json", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_atlas.json"))
    args = p.parse_args()
    names = args.scenarios.split(",") if args.scenarios else None
    seeds = tuple(int(s) for s in args.seeds.split(","))
    t0 = time.time()
    report = run_atlas(names=names, seeds=seeds)
    text = report_json(report)
    Path(args.report).write_text(text)
    print(f"# atlas report ({len(report['runs'])} runs) -> {args.report}")
    print("scenario,seed,analyzed,latency_p99,executors_peak")
    for r in report["runs"]:
        print(f"{r['scenario']},{r['seed']},{r['analyzed']},"
              f"{r['latency_p99']},{r['executors_peak']}")
    verdict = dict(report["gates"])
    print(f"verdict: {verdict}")
    out = {"gates": verdict,
           "atlas": report["atlas"],
           "report_bytes": len(text),
           "wall_seconds": round(time.time() - t0, 2)}
    Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    if not verdict["ledgers_closed"]:
        raise SystemExit("atlas gate FAILED: per-tenant loss ledgers did "
                         f"not close: {verdict['ledger_failures']}")
    if not verdict["all_runs_analyzed"]:
        raise SystemExit("atlas gate FAILED: silent scenario runs "
                         f"(nothing analyzed): {verdict['silent_runs']}")
