"""Keyed-shuffle / sharded fan-in benchmark — does the sharded data plane
actually relieve a hot-keyed single fan-in?

One seeded workload, two topologies, same records:

  single     the paper's single fan-in: 1 group -> 1 endpoint, no shards,
             producer-stream partitioning.  The lone endpoint's inbound
             bandwidth is the bottleneck.
  sharded    sharded data plane: N groups over N endpoints behind
             ``broker_shards`` group-owning broker shards, with the plan's
             shuffle edge re-partitioning records ACROSS producer streams
             by key (``shuffle_partitions``).  Per-shard telemetry feeds
             the controller (``shard_backlog_high``), whose scale-up
             decisions this study asserts.

The load is 1k virtual producer streams with deliberate hot-key skew:
80% of all records key onto 10% of the keys (10 hot keys out of 100), so
producer-partitioned dispatch concentrates work while keyed shuffle
spreads each hot key's records over one owned partition per key.

Gates, per seed:

  * throughput: the sharded run sustains >= 2x the single fan-in's
    records/virtual-second;
  * correctness: sink digests are byte-identical between the two
    topologies (same panes, same contents — sharding must not change
    results);
  * control loop: >= 1 controller scale-up decision in the sharded run is
    driven by per-shard telemetry (action reason ``shardN backlog=...``);
  * skew: the generated workload really is skewed (>= 80% of records on
    <= 10% of keys, measured from window-fire events).

CI runs this twice and byte-compares the emitted traces, so the sharded
path is deterministic end to end.

  PYTHONPATH=src python benchmarks/shuffle.py
      [--seeds 0] [--streams 1000] [--trace PATH] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.sim.scenario import LoadPhase, Scenario, run_scenario
from repro.streaming.operators import OperatorPipeline
from repro.workflow import ElasticityConfig, WorkflowConfig

HOT_KEYS = 10            # 10% of the key space...
COLD_KEYS = 90
HOT_FRACTION = 0.8       # ...receives 80% of the records
PHASES = (LoadPhase("steady", 2.0, 5.0), LoadPhase("drain", 0.5, 0.0))
N_SHARDS = 4
N_GROUPS = 8
SHUFFLE_PARTITIONS = 64
SHARD_BACKLOG_HIGH = 16
# per-endpoint inbound bandwidth (bytes/s): sized so the single fan-in is
# ingest-bound while the sharded fleet's aggregate (N_GROUPS endpoints)
# still has to queue — the per-shard backlog signal must actually fire
INBOUND_BW = 30_000.0


def make_key_fn(n_ranks: int):
    """Deterministic hot-key map, independent of group topology: the first
    HOT_FRACTION of ranks pool onto HOT_KEYS keys, the rest spread over
    COLD_KEYS keys.  Used by the plan's KeyBy — which is also the shuffle
    edge's routing function."""
    hot_ranks = int(n_ranks * HOT_FRACTION)

    def key_fn(stream_key: str, _rec) -> str:
        rank = int(stream_key.rsplit("/r", 1)[1])
        if rank < hot_ranks:
            return f"hot{rank % HOT_KEYS}"
        return f"cold{(rank - hot_ranks) % COLD_KEYS}"

    return key_fn


def make_pipeline(n_ranks: int):
    """Source KeyBy => the plan compiles to a shuffle edge.  The aggregate
    is order-insensitive and topology-blind ((rank, step, payload sum) —
    never group_id, which differs between the two modes) so sink digests
    compare across topologies."""
    key_fn = make_key_fn(n_ranks)

    def factory() -> OperatorPipeline:
        return (OperatorPipeline()
                .key_by("skew", key_fn)
                .tumbling_window("win", 0.5, allowed_lateness_s=5.0)
                .aggregate("agg", lambda k, vals: sorted(
                    (r.rank, r.step,
                     round(float(np.asarray(r.payload,
                                            np.float64).sum()), 6))
                    for r in vals))
                .sink("out"))

    return factory


def _workflow(n_ranks: int, sharded: bool) -> WorkflowConfig:
    base = dict(
        n_producers=n_ranks, compress="none", backpressure="block",
        queue_capacity=256, max_batch_records=32, inbound_bw=INBOUND_BW,
        trigger_interval=0.05, min_batch=4, n_executors=8,
        clock="virtual", flush_timeout_s=60.0)
    if not sharded:
        return WorkflowConfig(n_groups=1, n_endpoints=1, **base)
    return WorkflowConfig(
        n_groups=N_GROUPS, n_endpoints=N_GROUPS, broker_shards=N_SHARDS,
        shuffle_partitions=SHUFFLE_PARTITIONS,
        elasticity=ElasticityConfig(
            enabled=True, interval_s=0.05, cooldown_s=1.0,
            # fleet-level thresholds out of reach: ONLY the per-shard
            # signal can trigger scale-up in this study
            target_p99_s=1e9, backlog_high=10**9,
            shard_backlog_high=SHARD_BACKLOG_HIGH,
            min_executors=1, max_executors=12, adapt_batch=False,
            replace_stragglers=False, heartbeat_timeout_s=60.0),
        **base)


def _run(seed: int, n_ranks: int, sharded: bool):
    sc = Scenario(workflow=_workflow(n_ranks, sharded), phases=PHASES,
                  seed=seed, operators=make_pipeline(n_ranks),
                  payload_elems=16, flush_timeout_s=120.0)
    return run_scenario(sc)


def _skew_measured(trace) -> float:
    """Hot-key record share, measured from the window-fire events (every
    record lands in exactly one fired pane of the tumbling window)."""
    hot = total = 0
    for _, d in trace.events_of("op"):
        if d.get("event") != "window_fire":
            continue
        total += d["n"]
        if d["key"].startswith("hot"):
            hot += d["n"]
    return hot / total if total else 0.0


def _throughput(trace) -> float:
    return trace.summary["analyzed"] / trace.summary["virtual_duration_s"]


def main(seeds: list[int], n_ranks: int,
         trace_path: str | None = None) -> dict:
    rows, traces = [], []
    for seed in seeds:
        single = _run(seed, n_ranks, sharded=False)
        sharded = _run(seed, n_ranks, sharded=True)
        traces.append((seed, single, sharded))
        shard_scaleups = [
            d for _, d in sharded.events_of("action")
            if d["kind"] == "scale_up" and d["reason"].startswith("shard")]
        thr_single, thr_sharded = _throughput(single), _throughput(sharded)
        rows.append({
            "seed": seed,
            "streams": n_ranks,
            "records": sharded.summary["written"],
            "single_virtual_s": single.summary["virtual_duration_s"],
            "sharded_virtual_s": sharded.summary["virtual_duration_s"],
            "single_rps": round(thr_single, 3),
            "sharded_rps": round(thr_sharded, 3),
            "speedup": round(thr_sharded / thr_single, 3),
            "skew_hot_share": round(_skew_measured(sharded), 4),
            "shard_scale_ups": len(shard_scaleups),
            "shard_scale_reason": (shard_scaleups[0]["reason"]
                                   if shard_scaleups else None),
            "digest_match": (sharded.summary["sink_digest"]
                             == single.summary["sink_digest"]),
            "sink_digest": sharded.summary["sink_digest"][:16],
            "windows_closed": (single.summary["windows"]["closed"]
                               and sharded.summary["windows"]["closed"]),
            "dropped": (single.summary["dropped_by_policy"]
                        + sharded.summary["dropped_by_policy"]),
        })
    if trace_path:
        # both topologies' full event traces, concatenated across seeds —
        # CI's run-twice determinism gate byte-compares exactly this file
        with Path(trace_path).open("w") as fh:
            for seed, single, sharded in traces:
                fh.write(json.dumps({"seed": seed, "mode": "single",
                                     "digest": single.digest()}) + "\n")
                fh.write(single.to_jsonl())
                fh.write(json.dumps({"seed": seed, "mode": "sharded",
                                     "digest": sharded.digest()}) + "\n")
                fh.write(sharded.to_jsonl())
        print(f"# shuffle event traces -> {trace_path}")
    verdict = {
        "seeds": seeds,
        "streams": n_ranks,
        "min_speedup": min(r["speedup"] for r in rows),
        "speedup_ok": all(r["speedup"] >= 2.0 for r in rows),
        "digests_ok": all(r["digest_match"] for r in rows),
        "skew_ok": all(r["skew_hot_share"] >= HOT_FRACTION - 0.01
                       for r in rows),
        "shard_signal_ok": all(r["shard_scale_ups"] >= 1 for r in rows),
        "lossless": all(r["dropped"] == 0 and r["windows_closed"]
                        for r in rows),
    }
    print("seed,records,single_rps,sharded_rps,speedup,hot_share,"
          "shard_scale_ups,digest_match")
    for r in rows:
        print(f"{r['seed']},{r['records']},{r['single_rps']},"
              f"{r['sharded_rps']},{r['speedup']},{r['skew_hot_share']},"
              f"{r['shard_scale_ups']},{r['digest_match']}")
    print(f"verdict: {verdict}")
    return {"rows": rows, "verdict": verdict}


if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="0",
                   help="comma-separated VirtualClock seeds")
    p.add_argument("--streams", type=int, default=1000,
                   help="virtual producer streams (paper scale: 1k-10k)")
    p.add_argument("--trace", default=None,
                   help="write both topologies' event traces (jsonl) here")
    p.add_argument("--json", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_shuffle.json"))
    args = p.parse_args()
    t0 = time.time()
    out = main([int(s) for s in args.seeds.split(",")], args.streams,
               trace_path=args.trace)
    out["wall_seconds"] = round(time.time() - t0, 2)
    Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    print(f"# results -> {args.json} ({out['wall_seconds']}s wall)")
    v = out["verdict"]
    if not v["digests_ok"]:
        raise SystemExit("shuffle gate FAILED: sharded sink digest differs "
                         "from the single fan-in run")
    if not v["speedup_ok"]:
        raise SystemExit(f"shuffle gate FAILED: speedup "
                         f"{v['min_speedup']}x < 2x")
    if not v["shard_signal_ok"]:
        raise SystemExit("shuffle gate FAILED: no controller scale-up was "
                         "driven by per-shard telemetry")
    if not (v["skew_ok"] and v["lossless"]):
        raise SystemExit("shuffle gate FAILED: workload skew or loss "
                         "accounting check")
