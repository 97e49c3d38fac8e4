"""The paper's scaling deployment: synthetic ranks → broker → windowed DMD.

``producers`` ranks write one snapshot each per output step (the event
time is the step), bulk-synchronously, through ``FieldHandle.write_batch``
into ``producers / 16`` groups with 16 executors each (the paper's 16:1:16
producer:endpoint:executor ratio).  Payloads are the seeded low-rank model
of ``bench/payloads.py``, made before the window.  The cloud side is a
keyed tumbling window of ``pane_steps`` steps per rank into
``BatchAggregate(make_dmd_aggregate)``: no streaming eigensolve.

The traffic sets the pace: ``steps_per_s`` (open loop), or none, in which
case each step is written as soon as admission allows (saturation).  Either
way the benchmark's own admission gate (``bench/generator.py``) holds each
write until every rank's analysis is within the window's allowed lateness
of it, so that no record arrives too late for its pane.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks
from bench.generator import Admission, Schedule
from bench.harness import Check, Emitted, Outcome, pow2_buckets
from bench.payloads import snapshot_pool

PANE_SAMPLE = 256          # window panes compared per run


def run(ctx, config: dict, traffic: dict) -> Outcome:
    import jax

    from repro.analysis.dmd import batched_window_dmd, make_dmd_aggregate
    from repro.core import records
    from repro.workflow import OperatorPipeline, Session, WorkflowConfig

    P, d = config["producers"], config["record_floats"]
    an, wf = config["analysis"], config["workflow"]
    rank, rel_tol = an["rank"], an["rel_tol"]
    pane, late, pool_steps = an["pane_steps"], an["lateness_steps"], \
        config["pool_steps"]
    pool = snapshot_pool(ctx.seed, P, pool_steps, d, modes=config["modes"],
                         noise=config["noise"])

    # ---- compile every shape the traffic reaches -----------------------
    compile_s = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        compile_s[name] = time.perf_counter() - t0

    if jax.default_backend() == "tpu" and not records._pallas_rows_active():
        raise RuntimeError("the Pallas codec is not the rows codec on TPU")

    def codec(b):
        recs = [records.StreamRecord("warm", 0, r, 0, pool[r % P, 0])
                for r in range(b)]
        records.decode_batch(records.encode_batch(recs, compress=wf["compress"]))

    timed("codec", lambda: [codec(b) for b in range(2, wf["max_batch_records"] + 1)])
    # every pane holds exactly pane_steps; one watermark advance can fire
    # each rank's panes up to lateness + one pane back
    for k in pow2_buckets(P * (2 + late // pane)):
        panes = [pool[0, :pane]] * k
        timed(f"window_dmd_k{k}",
              lambda: batched_window_dmd(panes, rank=rank, n_features=d))
    ctx.log("compile_s " + " ".join(f"{k}={v:.3f}" for k, v in compile_s.items()))

    # ---- the deployment ---------------------------------------------------
    def pane_records(values):
        return sorted(values, key=lambda r: r.step)

    window_dmd = make_dmd_aggregate(
        rank=rank, n_features=d,
        prepare=lambda values: [r.payload for r in pane_records(values)])

    def window_stage(items):
        with ctx.span("window_solve"):
            eigs = window_dmd(items)
        return [{"steps": [r.step for r in pane_records(values)], "eigs": e}
                for (_key, values), e in zip(items, eigs)]

    pipeline = (OperatorPipeline(granularity="record")
                .key_by("rank", lambda key, rec: key)
                .tumbling_window("panes", size_s=float(pane),
                                 allowed_lateness_s=float(late))
                .batch_aggregate("window_dmd", window_stage)
                .sink("window_eigs"))
    session = Session(WorkflowConfig(n_producers=P, **wf), pipeline=pipeline)
    field = session.open_field("field", shape=(d,))
    keys = [records.StreamRecord("field", session.plan.group_of(r), r, 0,
                                 None).key() for r in range(P)]
    admission = Admission(session.exec_plan, keys, float(late), t_first=0.0)
    created: dict[int, float] = {}
    ranks = list(range(P))
    s = 0
    schedule = Schedule(traffic, time.time())

    def produce(until: float) -> None:
        nonlocal s
        while time.time() < until:
            schedule.wait(s)
            if not admission.wait(float(s), deadline=until):
                break
            with ctx.span("write"):
                created[s] = time.time()
                field.write_batch(s, list(pool[:, s % pool_steps]),
                                  ranks=ranks, t=float(s))
            s += 1

    def counters() -> dict:
        st = session.stats
        agg = session.engine.metrics()["batch_agg"].get("window_dmd", {})
        return {"sent": st.sent, "frames_sent": st.frames_sent,
                "agg_batches": agg.get("batches", 0),
                "agg_items": agg.get("items", 0), "writes": s,
                "admission_wait_s": admission.waited_s}

    try:
        produce(time.time() + traffic["warmup_s"])
        while not session.results("window_eigs"):
            produce(time.time() + 0.1)
        begin = counters()
        t0 = ctx.begin_window()
        produce(t0 + ctx.seconds)
        t1 = ctx.end_window()
        end = counters()
    finally:
        stats = session.close()
    window_out = session.results("window_eigs")
    ctx.log(schedule.report())

    # ---- what the window produced, against the reference ----------------
    def rank_of(key: str) -> int:
        return int(key.rsplit("/r", 1)[1])

    results = [Emitted("window", t, created[max(v["steps"])], len(v["steps"]))
               for _k, v, t in window_out]
    w_win = [(k, v) for k, v, t in window_out if t0 <= t <= t1]
    pick = np.random.default_rng([ctx.seed, 1])
    pairs = [(v["eigs"], pool[rank_of(k), np.asarray(v["steps"]) % pool_steps])
             for k, v in checks.sample(w_win, PANE_SAMPLE, pick)]
    t_ref = time.perf_counter()
    err, gap = checks.gap_ratio_median(pairs, rank, rel_tol, ctx, "window")
    ctx.log(f"reference: {len(pairs)} panes, {time.perf_counter() - t_ref:.1f}s")

    paned = np.zeros((P, s), bool)
    for k, v, _t in window_out:
        paned[rank_of(k), v["steps"]] = True
    raised = sum(isinstance(r.value, Exception) for r in session.results())
    late_dropped = \
        session.exec_plan.accounting()["windows"]["panes"]["late_dropped"]
    w_idx = [i for i in range(s) if t0 <= created[i] <= t1]
    checks_out = [
        Check("dropped", stats.dropped, 0),
        Check("unanalysed", int((~paned).sum()) + late_dropped, 0),
        Check("raised", raised, 0),
        Check("gap_ratio_median", err, an["limits"]["gap_ratio_median"]),
        Check("rank_gap", gap, 1),
    ]
    ctx.log(f"window {t1 - t0:.3f}s: {end['writes'] - begin['writes']} steps "
            f"written, {len(w_win)} panes emitted; admission waited "
            f"{end['admission_wait_s'] - begin['admission_wait_s']:.3f}s; "
            f"records dropped as late: {late_dropped}")
    # every micro-batch the engine took through the plan: when, how many
    processed = [(r.t_analyzed, r.n_records) for r in session.results()]
    per_s = np.zeros(int(np.ceil(t1 - t0)), int)
    for t, n in processed:
        if t0 <= t < t1:
            per_s[int(t - t0)] += n
    ctx.log("snapshots processed per second of the window: "
            + " ".join(map(str, per_s)))
    return Outcome(checks=checks_out, attempted=P * len(w_idx),
                   failed=int((~paned[:, w_idx]).sum()), results=results,
                   begin=begin, end=end, facts={"processed": processed})
