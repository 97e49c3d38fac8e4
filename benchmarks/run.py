"""Benchmark orchestrator — one section per paper table/figure.

  fig6        end-to-end simulation time: file vs broker vs sim-only (Fig 6)
  fig7        latency + aggregated throughput scaling (Fig 7a/7b)
  kernels     kernel-layer microbenchmarks
  roofline    the 40-cell dry-run roofline table (from artifacts)
  elasticity  closed-loop load-spike study (off by default; ~30s extra)

``python -m benchmarks.run [--only fig6,fig7,kernels,roofline,elasticity]
[--json PATH]``

Each section's rows are also written as JSON (default ``BENCH_run.json`` at
the repo root) so the BENCH trajectory is machine-readable PR over PR.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path


def _jsonable(obj):
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if hasattr(obj, "item"):       # numpy scalars
        return obj.item()
    return obj


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="fig6,fig7,kernels,roofline")
    p.add_argument("--json", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_run.json"))
    args = p.parse_args()
    want = set(args.only.split(","))
    failures = 0
    collected: dict = {}

    sections = []
    if "fig6" in want:
        from benchmarks import end_to_end
        sections.append(("fig6_end_to_end", end_to_end.main))
    if "fig7" in want:
        from benchmarks import scaling
        sections.append(("fig7_scaling", scaling.main))
    if "kernels" in want:
        from benchmarks import kernels_bench
        sections.append(("kernels", kernels_bench.main))
    if "roofline" in want:
        from benchmarks import roofline
        sections.append(("roofline", roofline.main))
    if "elasticity" in want:
        from benchmarks import elasticity
        sections.append(("elasticity", lambda: elasticity.main(smoke=True)))

    for name, fn in sections:
        print(f"\n# ==== {name} ====", flush=True)
        t0 = time.time()
        try:
            collected[name] = _jsonable(fn())
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
    if args.json and collected:
        Path(args.json).write_text(json.dumps(collected, indent=2) + "\n")
        print(f"# results -> {args.json}", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark sections failed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
