"""Elasticity benchmark — does the closed loop actually hold QoS for less?

One load-spike profile (low → spike → low) is replayed against three
provisioning strategies:

  static_low   executors fixed at the quiet-phase size.  Underprovisioned
               during the spike: backlog grows, generation→analysis p99
               blows through the target.
  static_peak  executors fixed at the spike size.  Holds the target, but
               pays peak executor-seconds for the whole run.
  elastic      ElasticController (telemetry bus + LatencyScalePolicy +
               BatchCapPolicy), min=1, max=peak.  The claim under test:
               it holds the configured p99 target through the spike while
               spending measurably fewer executor-seconds than static peak.

By default the study runs on **virtual time** (``repro.sim.scenario`` under
a seeded ``VirtualClock``): the whole three-mode suite finishes in a couple
of wall seconds, is deterministic (``--trace`` dumps the elastic run's
event trace; two same-seed invocations are byte-identical — CI's
``scenario-smoke`` job diffs them), and still exercises the real broker /
endpoints / engine / controller stack.  ``--wall`` switches back to the
original real-sleep mode for calibration against actual hardware.

Per-phase p99 is computed from records *generated* inside the phase window,
executor cost from the engine's executor-seconds integral.  Results land in
``BENCH_elasticity.json``.

  PYTHONPATH=src python benchmarks/elasticity.py [--smoke] [--wall]
      [--seed N] [--trace PATH] [--latency-trace PATH] [--json PATH]

``--latency-trace`` additionally dumps a record-level generation→analysis
latency curve per mode (``PATH-<mode>.jsonl``) — the raw material for
controller-policy regression sweeps, which virtual time makes ~free.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import math

import numpy as np

from repro.cloud import DEFAULT_CATALOG
from repro.launch.compile_cache import enable_compile_cache
from repro.sim.scenario import LoadPhase, Scenario, ScenarioRunner
from repro.streaming.engine import percentile_sorted
from repro.workflow import (ElasticityConfig, OperatorPipeline, Session,
                            WorkflowConfig)

N_RANKS = 4
FIELD_ELEMS = 256
ANALYZE_COST_S = 0.008          # simulated per-record analysis work
TARGET_P99_S = 1.5              # sits between elastic (~0.2s) and the
                                # underprovisioned static run (~3.5s)
BASE_EXECUTORS = 1              # quiet-phase provisioning
PEAK_EXECUTORS = 4              # spike provisioning
NODE_CLASS = "standard"         # cloud billing unit for node-seconds


def node_seconds_from_actions(n_exec0: int, duration_s: float,
                              actions) -> float:
    """Bill the run as if its executors lived on ``NODE_CLASS`` nodes:
    reconstruct alive(t) from the controller's scale actions and integrate
    whole-node occupancy (``ceil(alive / executors-per-node)``) over the
    run.  Cloud capacity comes in nodes, not executors — executor-seconds
    understate what a provider would actually charge for the fleet."""
    per = DEFAULT_CATALOG[NODE_CLASS].executors
    t_prev, alive, total = 0.0, n_exec0, 0.0
    for t, d in sorted(actions, key=lambda e: e[0]):
        if d["kind"] not in ("scale_up", "scale_down"):
            continue
        t = min(max(t, 0.0), duration_s)
        total += math.ceil(alive / per) * (t - t_prev)
        t_prev = t
        step = int(d.get("value") or 1)
        alive = max(1, alive + (step if d["kind"] == "scale_up" else -step))
    total += math.ceil(alive / per) * (duration_s - t_prev)
    return round(total, 6)


def _profile(smoke: bool) -> list[tuple[str, float, float]]:
    """(phase name, duration s, producer steps/s).  Each step writes
    N_RANKS records, so records/s = rate * N_RANKS."""
    if smoke:
        return [("low", 2.0, 5.0), ("spike", 4.0, 60.0), ("low", 3.0, 5.0)]
    return [("low", 5.0, 5.0), ("spike", 10.0, 60.0), ("low", 8.0, 5.0)]


def _workflow(mode: str) -> WorkflowConfig:
    elastic = mode == "elastic"
    n_exec = {"static_low": BASE_EXECUTORS, "static_peak": PEAK_EXECUTORS,
              "elastic": BASE_EXECUTORS}[mode]
    return WorkflowConfig(
        n_producers=N_RANKS, n_groups=2, executors_per_group=2,
        compress="none", backpressure="block", queue_capacity=4096,
        trigger_interval=0.05, min_batch=4, n_executors=n_exec,
        max_batch_records=8,
        elasticity=ElasticityConfig(
            enabled=elastic, interval_s=0.1, target_p99_s=TARGET_P99_S,
            min_executors=1, max_executors=PEAK_EXECUTORS, scale_up_step=2,
            backlog_high=24, idle_scale_down_s=1.0, cooldown_s=0.3))


# --------------------------------------------------------------- virtual mode
def _run_mode_virtual(mode: str, smoke: bool, seed: int,
                      record_latency: bool = False):
    """One provisioning strategy on deterministic simulated time; returns
    (result row, full event trace)."""
    sc = Scenario(
        workflow=_workflow(mode),
        phases=tuple(LoadPhase(name, dur, rate)
                     for name, dur, rate in _profile(smoke)),
        seed=seed, analysis_cost_s=ANALYZE_COST_S,
        payload_elems=FIELD_ELEMS, record_latency=record_latency)
    trace = ScenarioRunner(sc).run()
    s = trace.summary
    row = {
        "mode": mode,
        "records": s["sent"],
        "dropped": s["dropped_by_policy"],
        "p99_overall_s": s["latency_p99"],
        "p99_spike_s": trace.phase_p99("spike"),
        "p99_low_s": trace.phase_p99("low"),
        "executor_seconds": s["executor_seconds"],
        "node_seconds": node_seconds_from_actions(
            sc.workflow.n_executors, s["virtual_duration_s"],
            trace.events_of("action")),
        "executors_configured": sc.workflow.n_executors,
        "executors_peak_observed": max(s["executors_peak"],
                                       sc.workflow.n_executors),
        "virtual_duration_s": s["virtual_duration_s"],
    }
    row["node_cost"] = round(
        row["node_seconds"] * DEFAULT_CATALOG[NODE_CLASS].cost_rate, 6)
    if mode == "elastic":
        row["controller_actions"] = s.get("controller_actions", {})
    return row, trace


# ------------------------------------------------------------------ wall mode
def _run_mode_wall(mode: str, smoke: bool) -> dict:
    """The original real-sleep study (hardware calibration path)."""
    cfg = _workflow(mode)               # the one place the mode table lives
    elastic = cfg.elasticity.enabled
    n_exec = cfg.n_executors

    def analyze(key, records):
        time.sleep(ANALYZE_COST_S * len(records))
        return len(records)

    payload = np.zeros(FIELD_ELEMS, np.float32)
    phase_windows: list[tuple[str, float, float]] = []
    pipeline = OperatorPipeline(granularity="batch").map(
        "analyze", analyze, ordering="ordered")
    with Session(cfg, pipeline=pipeline) as sess:
        h = sess.open_field("load", shape=(FIELD_ELEMS,))
        step = 0
        for name, dur, rate in _profile(smoke):
            t0 = time.time()
            period = 1.0 / rate
            while True:
                now = time.time()
                if now - t0 >= dur:
                    break
                h.write_batch(step, [payload] * N_RANKS,
                              ranks=list(range(N_RANKS)))
                step += 1
                time.sleep(max(0.0, period - (time.time() - now)))
            phase_windows.append((name, t0, time.time()))
        sess.flush(timeout=60)
    # after close(): the controller thread is stopped, so the telemetry
    # history deque is safe to iterate
    exec_peak = max((s.alive_executors for s in sess.telemetry.history),
                    default=n_exec) if sess.telemetry is not None else n_exec
    results = sess.results()
    exec_secs = sess.engine.executor_seconds()

    def _phase_p99(name: str) -> float:
        lats = sorted(r.latency for r in results
                      for (pn, a, b) in phase_windows
                      if pn == name and a <= r.t_generated_min < b)
        return percentile_sorted(lats, 0.99)

    # node-seconds on wall time: integrate whole-node occupancy over the
    # telemetry history when the controller ran, else the static fleet
    per = DEFAULT_CATALOG[NODE_CLASS].executors
    t0_run, t1_run = phase_windows[0][1], phase_windows[-1][2]
    hist = list(sess.telemetry.history) if sess.telemetry is not None else []
    if len(hist) >= 2:
        node_secs = sum(
            np.ceil(max(a.alive_executors, 1) / per) * (b.t - a.t)
            for a, b in zip(hist, hist[1:]))
        node_secs += np.ceil(max(hist[0].alive_executors, 1) / per) \
            * max(0.0, hist[0].t - t0_run)
        node_secs += np.ceil(max(hist[-1].alive_executors, 1) / per) \
            * max(0.0, t1_run - hist[-1].t)
    else:
        node_secs = np.ceil(n_exec / per) * (t1_run - t0_run)
    row = {
        "mode": mode,
        "records": sess.stats.sent,
        "dropped": sess.stats.dropped,
        "p99_overall_s": sess.latency_stats().get("p99", float("nan")),
        "p99_spike_s": _phase_p99("spike"),
        "p99_low_s": _phase_p99("low"),
        "executor_seconds": exec_secs,
        "node_seconds": round(float(node_secs), 6),
        "node_cost": round(float(node_secs)
                           * DEFAULT_CATALOG[NODE_CLASS].cost_rate, 6),
        "executors_configured": n_exec,
        "executors_peak_observed": exec_peak,
    }
    if elastic and sess.controller is not None:
        row["controller_actions"] = sess.controller.summary()["actions"]
    return row


def main(smoke: bool = False, wall: bool = False, seed: int = 0,
         trace_path: str | None = None,
         latency_trace_path: str | None = None) -> dict:
    rows = []
    for m in ("static_low", "static_peak", "elastic"):
        if wall:
            rows.append(_run_mode_wall(m, smoke))
        else:
            row, trace = _run_mode_virtual(
                m, smoke, seed, record_latency=bool(latency_trace_path))
            rows.append(row)
            if m == "elastic" and trace_path:
                Path(trace_path).write_text(trace.to_jsonl())
                print(f"# elastic event trace -> {trace_path} "
                      f"(sha256 {trace.digest()[:16]}…)")
            if latency_trace_path:
                # one record-level latency curve PER MODE: the raw material
                # for controller-policy regression sweeps on virtual time
                curve = trace.latency_curve()
                out_path = Path(latency_trace_path)
                path = out_path.with_name(
                    f"{out_path.stem}-{m}{out_path.suffix or '.jsonl'}")
                path.write_text("".join(
                    json.dumps({"t": t, "latency": lat}) + "\n"
                    for t, lat in curve))
                print(f"# {m} record-latency curve ({len(curve)} records) "
                      f"-> {path}")
    by = {r["mode"]: r for r in rows}
    verdict = {
        "target_p99_s": TARGET_P99_S,
        "clock": "wall" if wall else "virtual",
        "seed": None if wall else seed,
        # the headline claims:
        "elastic_holds_target": by["elastic"]["p99_spike_s"] <= TARGET_P99_S,
        "static_low_breaches": by["static_low"]["p99_spike_s"] > TARGET_P99_S,
        "elastic_vs_peak_exec_seconds_ratio": (
            by["elastic"]["executor_seconds"]
            / max(by["static_peak"]["executor_seconds"], 1e-9)),
        # the cloud bill arrives in whole node-seconds, not executor-seconds
        "node_class": NODE_CLASS,
        "elastic_vs_peak_node_seconds_ratio": (
            by["elastic"]["node_seconds"]
            / max(by["static_peak"]["node_seconds"], 1e-9)),
    }
    out = {"rows": rows, "verdict": verdict}
    hdr = ("mode,records,dropped,p99_spike_s,p99_overall_s,"
           "executor_seconds,node_seconds,executors_peak_observed")
    print(hdr)
    for r in rows:
        print(f"{r['mode']},{r['records']},{r['dropped']},"
              f"{r['p99_spike_s']:.3f},{r['p99_overall_s']:.3f},"
              f"{r['executor_seconds']:.1f},{r['node_seconds']:.1f},"
              f"{r['executors_peak_observed']}")
    print(f"verdict: {verdict}")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="short CI profile (virtual: <2s wall; wall: ~10s/mode)")
    p.add_argument("--wall", action="store_true",
                   help="real-sleep mode (original study; minutes of wall "
                        "time) instead of deterministic virtual time")
    p.add_argument("--seed", type=int, default=0,
                   help="VirtualClock seed (virtual mode only)")
    p.add_argument("--trace", default=None,
                   help="write the elastic run's event trace (jsonl) here "
                        "(virtual mode only)")
    p.add_argument("--latency-trace", default=None,
                   help="write per-mode record-level latency curves "
                        "(PATH-<mode>.jsonl) for controller-policy "
                        "regression sweeps (virtual mode only)")
    p.add_argument("--json", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_elasticity.json"))
    args = p.parse_args()
    t0 = time.time()
    out = main(smoke=args.smoke, wall=args.wall, seed=args.seed,
               trace_path=args.trace, latency_trace_path=args.latency_trace)
    out["wall_seconds"] = round(time.time() - t0, 2)
    Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    print(f"# results -> {args.json} ({out['wall_seconds']}s wall)")
    if not out["verdict"]["elastic_holds_target"]:
        raise SystemExit("elastic run failed to hold the p99 target")
    if not out["verdict"]["static_low_breaches"]:
        raise SystemExit("static_low unexpectedly held the target — "
                         "the study lost its contrast")
