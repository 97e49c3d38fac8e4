"""Operator ordering-contract benchmark — what does the ticket cost?

One producer stream, four executors, a fixed per-record analysis cost, three
pipelines differing ONLY in the work stage's ordering contract:

  ordered     Map(ordering="ordered"): every micro-batch of the stream runs
              under the per-stream ordering ticket on its sticky executor —
              exactly-sequenced, hence serial per stream (what a plain
              per-batch callback gets).
  unordered   Map(ordering="unordered"): the compiled plan has no ordered
              suffix, so the engine spreads the stream's micro-batches
              across ALL executors with no ticket — intra-stream parallel.
  keyed       KeyBy shards records, the work stage stays order-insensitive:
              same parallel dispatch, per-key state consistency.

Runs on deterministic virtual time (seeded VirtualClock), so the measured
contrast is pure scheduling, not machine noise.  The claim under test (CI
gates on it): unordered and keyed stages reach >= 2x the ordered baseline's
intra-stream throughput on a multi-executor run, while the ordered run's
sink sequence stays exactly step-ordered.

  PYTHONPATH=src python benchmarks/operators.py [--seed N] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.clock import VirtualClock
from repro.workflow import OperatorPipeline, Session, WorkflowConfig

N_RECORDS = 128
WRITE_RATE_HZ = 200.0        # producer steps/s (write window ~0.64 s)
COST_S = 0.02                # simulated analysis cost per record
N_EXECUTORS = 4
MIN_RATIO = 2.0              # the acceptance gate


def _pipeline(mode: str, clock) -> OperatorPipeline:
    def work(key, rec):
        clock.sleep(COST_S)          # simulated per-record analysis
        return rec.step

    pipe = OperatorPipeline()
    if mode == "keyed":
        pipe.key_by("shard", lambda k, rec: f"s{rec.rank % 4}/{k}")
    pipe.map("work", work,
             ordering="ordered" if mode == "ordered" else "unordered")
    pipe.sink("out")
    return pipe


def run_mode(mode: str, seed: int) -> dict:
    clock = VirtualClock(seed=seed)
    clock.attach()
    cfg = WorkflowConfig(n_producers=1, n_groups=1, compress="none",
                         backpressure="block", queue_capacity=4096,
                         trigger_interval=0.02, min_batch=4,
                         n_executors=N_EXECUTORS,
                         clock="virtual", clock_seed=seed)
    sess = Session(cfg, pipeline=_pipeline(mode, clock), clock=clock)
    h = sess.open_field("f", shape=(16,))
    payload = np.zeros(16, np.float32)
    t0 = clock.now()
    for step in range(N_RECORDS):
        h.write(step, payload)
        clock.sleep(1.0 / WRITE_RATE_HZ)
    sess.flush(timeout=300.0)
    sess.close()
    dur = clock.now() - t0
    out = sess.exec_plan.results("out")
    steps = [v for _k, v, _t in out]
    m = sess.engine.metrics()
    return {
        "mode": mode,
        "records": len(out),
        "virtual_duration_s": round(dur, 6),
        "throughput_rps": round(len(out) / dur, 3),
        "serial_floor_s": N_RECORDS * COST_S,
        "executors": N_EXECUTORS,
        "plan_contract": sess.exec_plan.contract,
        "order_timeouts": m["order_timeouts"],
        # only meaningful for the ordered run (single stream, single key):
        "sink_seq_exact": steps == sorted(steps),
    }


def main(seed: int = 0) -> dict:
    rows = [run_mode(m, seed) for m in ("ordered", "unordered", "keyed")]
    by = {r["mode"]: r for r in rows}
    verdict = {
        "seed": seed,
        "unordered_vs_ordered": round(
            by["unordered"]["throughput_rps"]
            / max(by["ordered"]["throughput_rps"], 1e-9), 3),
        "keyed_vs_ordered": round(
            by["keyed"]["throughput_rps"]
            / max(by["ordered"]["throughput_rps"], 1e-9), 3),
        "min_ratio": MIN_RATIO,
        "ordered_seq_exact": by["ordered"]["sink_seq_exact"],
        "records_complete": all(r["records"] == N_RECORDS for r in rows),
    }
    print("mode,records,virtual_s,throughput_rps,contract")
    for r in rows:
        print(f"{r['mode']},{r['records']},{r['virtual_duration_s']:.3f},"
              f"{r['throughput_rps']:.1f},{r['plan_contract']}")
    print(f"verdict: {verdict}")
    return {"rows": rows, "verdict": verdict}


if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_operators.json"))
    args = p.parse_args()
    t0 = time.time()
    out = main(seed=args.seed)
    out["wall_seconds"] = round(time.time() - t0, 2)
    Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    print(f"# results -> {args.json} ({out['wall_seconds']}s wall)")
    v = out["verdict"]
    if not v["records_complete"]:
        raise SystemExit("lost records — the contracts must not drop work")
    if not v["ordered_seq_exact"]:
        raise SystemExit("ordered contract broke per-stream sequencing")
    if min(v["unordered_vs_ordered"], v["keyed_vs_ordered"]) < MIN_RATIO:
        raise SystemExit(
            f"intra-stream parallel speedup below {MIN_RATIO}x: "
            f"unordered {v['unordered_vs_ordered']}x, "
            f"keyed {v['keyed_vs_ordered']}x")
