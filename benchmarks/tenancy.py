"""Multi-tenant QoS study — does the tenancy plane actually protect SLOs?

One seeded capacity squeeze (per-endpoint inbound bandwidth capped well
below the offered load), two control planes, same records:

  debt      the QoS plane as shipped: ``alerts`` declares priority 2, a
            0.5s p99 target and weight 4; ``batch`` is best-effort
            priority 0 with 3x the traffic.  Priority admission parks and
            (park-overflow) evicts batch at the shard high-water mark, and
            the ``SloDebtScalePolicy`` weighs scale decisions by
            accumulated per-tenant SLO debt.

  global    the same traffic with tenancy neutralized: both tenants ride
            at priority 0 (nobody parks, eviction is plain oldest-first)
            and scaling follows the single global p99 target — the
            pre-tenancy behavior, with per-tenant accounting kept on so
            the damage is measurable.

Gates, per seed:

  * SLO hold: in debt mode, the p99-targeted tenant's squeeze-phase p99
    stays under its target AND it loses nothing (no drops, no evictions);
  * graceful degradation: debt mode parks/evicts ONLY best-effort batch
    traffic, and its loss ledger closes exactly
    (admitted == sent + evicted);
  * contrast: global mode breaches — the alerts tenant's squeeze-phase
    p99 crosses its target or its records get evicted with everyone
    else's;
  * closure: per-tenant ledgers close in BOTH modes (loss is always
    attributed, never silent).

CI runs this twice and byte-compares the emitted traces, so the whole
QoS plane is deterministic end to end.

  PYTHONPATH=src python benchmarks/tenancy.py
      [--seeds 0] [--trace PATH] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache
from repro.sim.scenario import LoadPhase, Scenario, TenantTraffic, run_scenario
from repro.tenancy import TenantSpec
from repro.workflow import ElasticityConfig, WorkflowConfig

P99_TARGET_S = 0.5
SQUEEZE = "squeeze"
PHASES = (LoadPhase("calm", 1.0, 10.0),
          LoadPhase(SQUEEZE, 2.0, 40.0),
          LoadPhase("recover", 1.0, 10.0),
          LoadPhase("drain", 4.0, 0.0))
TRAFFIC = (TenantTraffic("alerts", ranks=(0,), every=2),
           TenantTraffic("batch", ranks=(1, 2, 3)))


def _workflow(mode: str) -> WorkflowConfig:
    if mode == "debt":
        tenants = (TenantSpec("alerts", priority=2,
                              p99_target_s=P99_TARGET_S, weight=4.0),
                   TenantSpec("batch", priority=0))
        # fleet-global thresholds out of reach: only per-tenant SLO debt
        # can drive scale-up in this mode
        elastic = ElasticityConfig(
            enabled=True, interval_s=0.1, slo_debt=True,
            target_p99_s=1e9, backlog_high=10**9, adapt_batch=False,
            min_executors=1, max_executors=8, cooldown_s=0.5,
            heartbeat_timeout_s=60.0, replace_stragglers=False)
    else:
        # same declared tenants, QoS neutralized: equal priority means
        # nobody parks and eviction is oldest-first across tenants; the
        # single global target drives scaling
        tenants = (TenantSpec("alerts", priority=0,
                              p99_target_s=P99_TARGET_S, weight=4.0),
                   TenantSpec("batch", priority=0))
        elastic = ElasticityConfig(
            enabled=True, interval_s=0.1, target_p99_s=P99_TARGET_S,
            backlog_high=10**9, adapt_batch=False,
            min_executors=1, max_executors=8, cooldown_s=0.5,
            heartbeat_timeout_s=60.0, replace_stragglers=False)
    return WorkflowConfig(
        n_producers=4, n_groups=2, compress="none",
        queue_capacity=32, max_batch_records=2, inbound_bw=4_000.0,
        backpressure="drop_oldest", qos_high_water=0.3,
        trigger_interval=0.05, min_batch=2, n_executors=2,
        clock="virtual", flush_timeout_s=120.0,
        tenants=tenants, elasticity=elastic)


def _run(seed: int, mode: str):
    sc = Scenario(workflow=_workflow(mode), phases=PHASES,
                  tenant_traffic=TRAFFIC, analysis_cost_s=0.001,
                  payload_elems=32, seed=seed)
    return run_scenario(sc)


def main(seeds: list[int], trace_path: str | None = None) -> dict:
    rows, traces = [], []
    for seed in seeds:
        debt = _run(seed, "debt")
        glob = _run(seed, "global")
        traces.append((seed, debt, glob))
        dt, gt = debt.summary["tenants"], glob.summary["tenants"]
        rows.append({
            "seed": seed,
            "debt_alerts_squeeze_p99": round(
                debt.phase_p99(SQUEEZE, tenant="alerts"), 6),
            "debt_batch_squeeze_p99": round(
                debt.phase_p99(SQUEEZE, tenant="batch"), 6),
            "global_alerts_squeeze_p99": round(
                glob.phase_p99(SQUEEZE, tenant="alerts"), 6),
            "debt_alerts_lost": (dt["alerts"]["dropped"]
                                 + dt["alerts"]["evicted"]),
            "global_alerts_lost": (gt["alerts"]["dropped"]
                                   + gt["alerts"]["evicted"]),
            "debt_batch_parked": dt["batch"]["parked_total"],
            "debt_batch_evicted": dt["batch"]["evicted"],
            "debt_batch_analyzed": dt["batch"]["analyzed"],
            "debt_ledger_closed": debt.summary["tenant_ledger"]["closed"],
            "global_ledger_closed": glob.summary["tenant_ledger"]["closed"],
        })
    if trace_path:
        with Path(trace_path).open("w") as fh:
            for seed, debt, glob in traces:
                fh.write(json.dumps({"seed": seed, "mode": "debt",
                                     "digest": debt.digest()}) + "\n")
                fh.write(debt.to_jsonl())
                fh.write(json.dumps({"seed": seed, "mode": "global",
                                     "digest": glob.digest()}) + "\n")
                fh.write(glob.to_jsonl())
        print(f"# tenancy event traces -> {trace_path}")
    verdict = {
        "seeds": seeds,
        "p99_target_s": P99_TARGET_S,
        "slo_held": all(r["debt_alerts_squeeze_p99"] <= P99_TARGET_S
                        and r["debt_alerts_lost"] == 0 for r in rows),
        "graceful": all((r["debt_batch_parked"] + r["debt_batch_evicted"]) > 0
                        and r["debt_batch_analyzed"] > 0 for r in rows),
        "global_breaches": all(
            r["global_alerts_squeeze_p99"] > P99_TARGET_S
            or r["global_alerts_lost"] > 0 for r in rows),
        "ledgers_closed": all(r["debt_ledger_closed"]
                              and r["global_ledger_closed"] for r in rows),
    }
    print("seed,debt_alerts_p99,global_alerts_p99,debt_alerts_lost,"
          "global_alerts_lost,batch_parked,batch_evicted")
    for r in rows:
        print(f"{r['seed']},{r['debt_alerts_squeeze_p99']},"
              f"{r['global_alerts_squeeze_p99']},{r['debt_alerts_lost']},"
              f"{r['global_alerts_lost']},{r['debt_batch_parked']},"
              f"{r['debt_batch_evicted']}")
    print(f"verdict: {verdict}")
    return {"rows": rows, "verdict": verdict}


if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="0",
                   help="comma-separated VirtualClock seeds")
    p.add_argument("--trace", default=None,
                   help="write both modes' event traces (jsonl) here")
    p.add_argument("--json", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_tenancy.json"))
    args = p.parse_args()
    t0 = time.time()
    out = main([int(s) for s in args.seeds.split(",")],
               trace_path=args.trace)
    out["wall_seconds"] = round(time.time() - t0, 2)
    Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    v = out["verdict"]
    if not v["ledgers_closed"]:
        raise SystemExit("tenancy gate FAILED: a per-tenant loss ledger "
                         "did not close — loss went unattributed")
    if not v["slo_held"]:
        raise SystemExit("tenancy gate FAILED: debt-weighted control let "
                         "the protected tenant breach its p99 target or "
                         "lose records")
    if not v["graceful"]:
        raise SystemExit("tenancy gate FAILED: best-effort traffic was not "
                         "degraded gracefully (no parking/eviction, or "
                         "starved outright)")
    if not v["global_breaches"]:
        raise SystemExit("tenancy gate FAILED: the tenancy-neutralized "
                         "baseline held the SLO — the squeeze is not "
                         "actually squeezing")
