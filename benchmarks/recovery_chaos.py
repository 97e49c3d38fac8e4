"""Recovery chaos benchmark — is exactly-once delivery actually exact?

Per seed, two runs of the same seeded workload:

  fault-free   no faults, no checkpoints.  Produces the oracle: the sink
               contents (window aggregates per key) a correct run emits.
  chaos        kill an executor, crash the broker twice, fail an endpoint
               mid-replay, and kill the WHOLE session (checkpoint restore
               + WAL tail replay) — all mid-run, on virtual time.

The gate, per seed:

  * the loss ledger closes: analyzed == written, nothing dropped by
    policy, no frame ever abandoned;
  * the chaos run's sink digest is byte-identical to the fault-free
    run's — every record applied exactly once, in the same windows.

CI runs this twice and diffs the emitted event traces byte-for-byte, so
the recovery path itself (not just its end state) is deterministic.

  PYTHONPATH=src python benchmarks/recovery_chaos.py
      [--seeds 0,1,2] [--trace PATH] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.sim.scenario import Fault, LoadPhase, Scenario, run_scenario
from repro.streaming.operators import OperatorPipeline
from repro.workflow import ElasticityConfig, WorkflowConfig

N_RANKS = 4
PHASES = (LoadPhase("steady", 3.0, 20.0), LoadPhase("drain", 2.5, 0.0))
FAULTS = (Fault(t=0.45, kind="kill_executor", target=1),
          Fault(t=0.65, kind="kill_broker"),       # mid-window
          Fault(t=0.95, kind="fail_endpoint", target=0),
          Fault(t=1.55, kind="kill_session"),      # checkpoint restore
          Fault(t=2.1, kind="kill_executor", target=0),
          Fault(t=2.6, kind="kill_broker"))
CHECKPOINT_EVERY_S = 0.6


def _workflow() -> WorkflowConfig:
    return WorkflowConfig(
        n_producers=N_RANKS, n_groups=2, executors_per_group=2,
        compress="none", backpressure="block", queue_capacity=4096,
        trigger_interval=0.05, min_batch=4, n_executors=2,
        max_batch_records=8, delivery="exactly-once", clock="virtual",
        flush_timeout_s=60.0,
        elasticity=ElasticityConfig(
            enabled=True, interval_s=0.1, heartbeat_timeout_s=0.5,
            min_executors=1, max_executors=4, cooldown_s=0.3))


def _pipeline() -> OperatorPipeline:
    return (OperatorPipeline()
            .map("norm", lambda k, rec: (rec.step,
                 round(float(np.asarray(rec.payload,
                                        dtype=np.float64).sum()), 6)))
            .key_by("bygroup", lambda k, v: k.split("/")[1])
            .tumbling_window("win", 0.5, allowed_lateness_s=1.0)
            .aggregate("agg", lambda k, vals: sorted(vals))
            .sink("out"))


def _run(seed: int, chaos: bool):
    sc = Scenario(workflow=_workflow(), phases=PHASES, seed=seed,
                  operators=_pipeline,
                  faults=FAULTS if chaos else (),
                  checkpoint_every_s=CHECKPOINT_EVERY_S if chaos else 0.0)
    return run_scenario(sc)


def main(seeds: list[int], trace_path: str | None = None) -> dict:
    rows, traces = [], []
    for seed in seeds:
        clean = _run(seed, chaos=False)
        chaos = _run(seed, chaos=True)
        traces.append((seed, chaos))
        c, f = clean.summary, chaos.summary
        row = {
            "seed": seed,
            "written": f["written"],
            "analyzed": f["analyzed"],
            "dropped_by_policy": f["dropped_by_policy"],
            "frames_abandoned": f["recovery"]["frames_abandoned"],
            "frames_replayed": f["recovery"]["frames_replayed"],
            "records_replayed": f["recovery"]["records_replayed"],
            "records_deduped": f["recovery"]["records_deduped"],
            "checkpoints": f["recovery"]["checkpoints"],
            "session_restores": f["recovery"]["session_restores"],
            "ledger_closed": (f["analyzed"] == f["written"]
                              and f["dropped_by_policy"] == 0
                              and f["recovery"]["frames_abandoned"] == 0),
            "windows_closed": f["windows"]["closed"],
            "digest_match": f["sink_digest"] == c["sink_digest"],
            "sink_digest": f["sink_digest"][:16],
        }
        rows.append(row)
    if trace_path:
        # one concatenated jsonl across seeds, so CI's run-twice
        # determinism gate is a single byte-for-byte cmp
        with Path(trace_path).open("w") as fh:
            for seed, tr in traces:
                fh.write(json.dumps({"seed": seed,
                                     "digest": tr.digest()}) + "\n")
                fh.write(tr.to_jsonl())
        print(f"# chaos event traces -> {trace_path}")
    verdict = {
        "seeds": seeds,
        "exactly_once": all(r["ledger_closed"] and r["digest_match"]
                            and r["windows_closed"] for r in rows),
        "total_records_replayed": sum(r["records_replayed"] for r in rows),
        "total_session_restores": sum(r["session_restores"] for r in rows),
    }
    hdr = ("seed,written,analyzed,replayed,deduped,checkpoints,restores,"
           "ledger_closed,digest_match")
    print(hdr)
    for r in rows:
        print(f"{r['seed']},{r['written']},{r['analyzed']},"
              f"{r['records_replayed']},{r['records_deduped']},"
              f"{r['checkpoints']},{r['session_restores']},"
              f"{r['ledger_closed']},{r['digest_match']}")
    print(f"verdict: {verdict}")
    return {"rows": rows, "verdict": verdict}


if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="0,1,2",
                   help="comma-separated VirtualClock seeds")
    p.add_argument("--trace", default=None,
                   help="write the chaos runs' event traces (jsonl) here")
    p.add_argument("--json", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_recovery_chaos.json"))
    args = p.parse_args()
    t0 = time.time()
    out = main([int(s) for s in args.seeds.split(",")],
               trace_path=args.trace)
    out["wall_seconds"] = round(time.time() - t0, 2)
    Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    print(f"# results -> {args.json} ({out['wall_seconds']}s wall)")
    if not out["verdict"]["exactly_once"]:
        raise SystemExit("exactly-once gate FAILED: a chaos run lost, "
                         "duplicated, or re-windowed records")
    if out["verdict"]["total_session_restores"] < len(out["rows"]):
        raise SystemExit("chaos plan did not exercise session restore")
