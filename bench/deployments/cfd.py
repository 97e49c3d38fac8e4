"""The paper's in-situ workflow: CFD ranks → broker → per-region streaming DMD.

A wind-around-buildings run (``sim/cfd.py``) on the host's CPU devices, the
HPC side, has its domain cut into ``regions`` slabs along Z.  Every
``write_every``-th step it writes each slab's velocity (u and w,
2·(nz/regions)·nx floats) as one record per region through
``FieldHandle.write_batch``: one broker group, one endpoint and one
executor per region (the paper's 16:1:16 at 16 regions).  The cloud side
keys by region and runs an ordered map stage with one ``StreamingDMD`` per
region on the chip: each micro-batch is folded into the region's
device-resident G and A (the Pallas ``gram_pair`` kernel on TPU) and
followed by one eigensolve — ``exact_dmd`` while the region has seen at
most ``window`` snapshots, then the d×d ``eigh`` of ``_gram_operator``.

The simulation steps as fast as the gate allows (or at ``steps_per_s``).
The gate (``Horizon``) holds the write of output step s until at most
``horizon_steps`` output steps are written and not yet analysed by every
region: at one step, s is written once every region has analysed s − 1,
the coupling of a staging transport whose queue holds one step and blocks
the writer.  It reads what each region analysed from the stage itself: the
plan commits a micro-batch before the ordered stage runs it, so the plan's
frontier runs ahead of the analysis.

Every slab is kept on the host as written.  Each record the stage
received is compared with its slab as the int8 codec's documented loss
makes it (``reference.int8_roundtrip``), to within one code step of its
block, and each sampled result with the plain reference over its region's
whole history up to the result's last step.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import checks, reference
from bench.generator import Schedule
from bench.harness import Check, Emitted, Outcome, pow2_buckets

RESULT_SAMPLE = 64         # window results compared per run
DRAIN_S = 900.0            # longest wait for the warm-up, or for the backlog
STALL_S = 1.0              # engine idle this long with the gate shut: stalled


class Horizon:
    """Blocks the write of output step ``o`` until writing it leaves at most
    ``horizon`` output steps written and not yet analysed by the slowest
    region: ``o - analysed.min() <= horizon``, where ``analysed`` holds each
    region's last analysed output step (-1 before the first)."""

    def __init__(self, analysed: np.ndarray, horizon: int,
                 poll_s: float = 0.002):
        self.analysed = analysed
        self.horizon = horizon
        self.poll_s = poll_s
        self.waited_s = 0.0

    def open(self, o: int) -> bool:
        return o - self.analysed.min() <= self.horizon

    def wait(self, o: int, deadline: float) -> bool:
        """Wait until ``o`` may be written; False if ``deadline`` (wall
        time) passed first."""
        if self.open(o):
            return True
        t0 = time.time()
        try:
            while not self.open(o):
                if time.time() >= deadline:
                    return False
                time.sleep(self.poll_s)
            return True
        finally:
            self.waited_s += time.time() - t0


def corrupted_rows(got: np.ndarray, written: np.ndarray) -> int:
    """How many of the (n, d) rows ``got`` differ from ``written`` as the
    int8 codec delivers it by more than one code step of their block (its
    scale, max|block|/127): a rounding tie or a last-bit difference in the
    scale moves a float by one step at most, a corrupted one by more."""
    q = reference.QBLOCK
    n, d = written.shape
    nb = -(-d // q)
    blocks = np.abs(np.pad(np.asarray(written, np.float32),
                           ((0, 0), (0, nb * q - d)))).reshape(n, nb, q)
    step = np.repeat(blocks.max(axis=2) / np.float32(127.0), q,
                     axis=1)[:, :d]
    off = np.abs(np.asarray(got, np.float64)
                 - reference.int8_roundtrip(written))
    return int((off > 1.001 * step + 1e-30).any(axis=1).sum())


def run(ctx, config: dict, traffic: dict) -> Outcome:
    import jax

    from repro.analysis.dmd import StreamingDMD, exact_dmd
    from repro.core import records
    from repro.sim.cfd import CFDConfig, init_state, region_fields, step
    from repro.workflow import OperatorPipeline, Session, WorkflowConfig

    grid, an, wf = config["grid"], config["analysis"], config["workflow"]
    cfg = CFDConfig(nx=grid["nx"], nz=grid["nz"], n_regions=grid["regions"],
                    dt=grid["dt"], viscosity=grid["viscosity"],
                    inflow=grid["inflow"],
                    pressure_iters=grid["pressure_iters"])
    R = cfg.n_regions
    d = 2 * (cfg.nz // R) * cfg.nx
    if d != config["record_floats"]:
        raise ValueError(f"the grid gives {d} floats a slab, the "
                         f"configuration states {config['record_floats']}")
    rank, window, rel_tol = an["rank"], an["window"], an["rel_tol"]
    every, horizon = traffic["write_every"], traffic["horizon_steps"]
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng([ctx.seed, 0])

    # ---- compile every shape the traffic reaches -----------------------
    compile_s = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        compile_s[name] = time.perf_counter() - t0

    # the simulation's state lives on the host's CPU device, so its step
    # compiles and runs there; a seeded disturbance of the initial velocity
    # field makes the flow, and so every snapshot, the seed's own
    state0 = init_state(cfg)
    mask = np.asarray(state0["mask"])
    state0 = dict(state0,
                  u=np.asarray(state0["u"]) + np.float32(0.05) * mask
                  * rng.standard_normal(mask.shape, dtype=np.float32),
                  w=np.float32(0.05) * mask
                  * rng.standard_normal(mask.shape, dtype=np.float32))
    state0 = jax.device_put(state0, cpu)
    timed("cfd_step", lambda: step(state0, cfg))
    warm = rng.standard_normal((window + 4 * horizon + 2, d),
                               dtype=np.float32)

    def codec(b):
        recs = [records.StreamRecord("warm", 0, r, 0, warm[r % len(warm)])
                for r in range(b)]
        records.decode_batch(records.encode_batch(recs, compress=wf["compress"]))

    timed("codec", lambda: [codec(b) for b in range(1, wf["max_batch_records"] + 1)])
    # a region's micro-batch holds at most the horizon's steps, chained to
    # the previous one: one gram_pair variant per power-of-two bucket of
    # pair rows; then one row at a time past the window, to the Gram route
    sd = StreamingDMD(n_features=d, window=window, rank=rank)
    sd.update_batch(warm[:1])
    i = 1
    for m in pow2_buckets(horizon):
        timed(f"gram_pair_{m}", lambda: sd.update_batch(warm[i:i + m]))
        i += m
    for n in range(3, window + 1):
        timed(f"exact_dmd_{n}",
              lambda: exact_dmd(np.ascontiguousarray(warm[:n].T), rank=rank))
    while sd.n_seen <= window:
        sd.update_batch(warm[i:i + 1])
        i += 1
    timed("gram_operator", sd.eigenvalues)
    del sd
    ctx.log("compile_s " + " ".join(f"{k}={v:.3f}" for k, v in compile_s.items()))

    # ---- the deployment ---------------------------------------------------
    states = [StreamingDMD(n_features=d, window=window, rank=rank)
              for _ in range(R)]
    analysed = np.full(R, -1, np.int64)     # last output step each folded
    tally = {"updates": 0, "rows": 0, "ahead": 0}
    received = [{} for _ in range(R)]       # per region: step -> payload
    lock = threading.Lock()
    last_written = [-1]                     # the newest output step written

    def region_of(key: str) -> int:
        return int(key.rsplit("/r", 1)[1])

    def stage(key, batch):
        region = region_of(key)
        sd = states[region]
        recs = sorted(batch, key=lambda r: r.step)
        for rec in recs:
            received[region][rec.step] = rec.payload
        with ctx.span("stream_update"):
            sd.update_batch(np.stack([r.payload for r in recs]))
        with ctx.span("stream_solve"):
            eigs = sd.eigenvalues()
        with lock:
            tally["updates"] += 1
            tally["rows"] += len(recs)
            # steps written beyond this micro-batch's newest, before the
            # gate learns of it: the horizon keeps it below ``horizon``
            tally["ahead"] = max(tally["ahead"],
                                 last_written[0] - recs[-1].step)
        analysed[region] = recs[-1].step
        return {"first_step": recs[0].step, "last_step": recs[-1].step,
                "rows": len(recs), "n_seen": sd.n_seen, "eigs": eigs}

    pipeline = (OperatorPipeline(granularity="batch")
                .key_by("region", lambda key, batch: key)
                .map("streaming_dmd", stage, ordering="ordered")
                .sink("stream_eigs"))
    session = Session(WorkflowConfig(n_producers=R, **wf), pipeline=pipeline)
    field = session.open_field("velocity", shape=(d,))
    gate = Horizon(analysed, horizon)
    schedule = Schedule(traffic, time.time())
    ranks = list(range(R))
    written: list[list[np.ndarray]] = []    # per output step, per region
    created: list[float] = []
    sim_s = []
    o = 0
    state = state0

    def produce(until: float) -> None:
        nonlocal o, state
        while time.time() < until:
            schedule.wait(o)
            if not gate.wait(o, deadline=until):
                break
            t_sim = time.perf_counter()
            with ctx.span("simulate"):
                for _ in range(every):
                    state = step(state, cfg)
                slabs = region_fields(state, cfg)
            sim_s.append(time.perf_counter() - t_sim)
            with ctx.span("write"):
                created.append(time.time())
                written.append(slabs)
                with lock:
                    last_written[0] = o
                field.write_batch(o, slabs, ranks=ranks,
                                  t=o * every * cfg.dt)
            o += 1

    def engine_idle() -> bool:
        eng = session.engine
        m = eng.metrics()
        return (sum(ep.pending() for ep in eng.endpoints) == 0
                and m["held_records"] == 0 and m["queued"] == 0
                and all(e["current_key"] is None for e in m["executors"]))

    def counters() -> dict:
        with lock:
            return {"updates": tally["updates"], "rows": tally["rows"],
                    "writes": o, "admission_wait_s": gate.waited_s}

    drain_s = None
    try:
        # warm up until every region has passed its snapshot window, so
        # that only the Gram route runs in the window; a region that stops
        # analysing (the engine idle, the gate shut) ends the warm-up, and
        # the checks report what it left unanalysed
        t_warm = idle_since = time.time()
        produce(t_warm + traffic["warmup_s"])
        while min(s.n_seen for s in states) <= window:
            if time.time() - t_warm > DRAIN_S:
                raise RuntimeError("warm-up: a region has not passed its "
                                   f"snapshot window after {DRAIN_S:.0f}s")
            if gate.open(o) or not engine_idle():
                idle_since = time.time()
            elif time.time() - idle_since > STALL_S:
                ctx.log(f"warm-up stalled at output step {o}: analysed "
                        f"{analysed.tolist()}")
                break
            produce(time.time() + 0.1)
        begin = counters()
        t0 = ctx.begin_window()
        produce(t0 + ctx.seconds)
        t1 = ctx.end_window()
        end = counters()
        # what was written is analysed before the close, unless the engine
        # has had nothing to run for STALL_S
        t_drain = idle_since = time.time()
        session.flush(timeout=60.0)
        while analysed.min() < o - 1 and time.time() - t_drain < DRAIN_S:
            if not engine_idle():
                idle_since = time.time()
            elif time.time() - idle_since > STALL_S:
                break
            time.sleep(0.01)
        drain_s = time.time() - t_drain
    finally:
        stats = session.close()
    stream_out = session.results("stream_eigs")
    ctx.log(schedule.report())

    # ---- what the window produced, against the reference ----------------
    history = [np.stack([slabs[r] for slabs in written]) for r in range(R)]
    results = [Emitted("stream", t, created[v["last_step"]], v["rows"])
               for _k, v, t in stream_out]
    w_out = [(k, v) for k, v, t in stream_out if t0 <= t <= t1]
    pick = np.random.default_rng([ctx.seed, 1])
    pairs = [(v["eigs"], history[region_of(k)][: v["last_step"] + 1])
             for k, v in checks.sample(w_out, RESULT_SAMPLE, pick)]
    t_ref = time.perf_counter()
    err, gap = checks.gap_ratio_median(pairs, rank, rel_tol, ctx, "stream")
    ctx.log(f"reference: {len(pairs)} results, "
            f"{time.perf_counter() - t_ref:.1f}s")

    # each region's results continue its previous one, step by step
    prev = np.full(R, -1, np.int64)
    out_of_order = 0
    for k, v, _t in stream_out:
        r = region_of(k)
        out_of_order += v["first_step"] != prev[r] + 1 \
            or v["last_step"] - v["first_step"] + 1 != v["rows"]
        prev[r] = v["last_step"]
    # every snapshot written is folded in by the close: each region has
    # seen as many as were written, and its last result ends at the last
    unanalysed = sum(abs(o - s.n_seen) for s in states) \
        + int(np.abs(o - 1 - prev).sum())
    raised = sum(isinstance(r.value, Exception) for r in session.results())
    corrupted = 0
    for r in range(R):
        steps = sorted(received[r])
        if steps:
            corrupted += corrupted_rows(
                np.stack([received[r][i] for i in steps]), history[r][steps])
    checks_out = [
        Check("dropped", stats.dropped, 0),
        Check("unanalysed", unanalysed, 0),
        Check("raised", raised, 0),
        Check("out_of_order", out_of_order, 0),
        Check("corrupted", corrupted, 0),
        Check("gap_ratio_median", err, an["limits"]["gap_ratio_median"]),
        Check("rank_gap", gap, 1),
    ]
    w_steps = [i for i in range(o) if t0 <= created[i] <= t1]
    ctx.log(f"window {t1 - t0:.3f}s: {end['writes'] - begin['writes']} "
            f"output steps written ({len(w_steps)} created in it), "
            f"{len(w_out)} results; {end['updates'] - begin['updates']} "
            f"updates of {end['rows'] - begin['rows']} snapshots; the gate "
            f"waited {end['admission_wait_s'] - begin['admission_wait_s']:.3f}s;"
            f" simulation {1e3 * float(np.median(sim_s)):.3f} ms per output "
            f"step (median, {every} steps); at most {tally['ahead']} steps "
            f"written past the one analysed; backlog analysed {drain_s}s "
            "after the window")
    processed = [(r.t_analyzed, r.n_records) for r in session.results()]
    per_s = np.zeros(int(np.ceil(t1 - t0)), int)
    for t, n in processed:
        if t0 <= t < t1:
            per_s[int(t - t0)] += n
    ctx.log("snapshots processed per second of the window: "
            + " ".join(map(str, per_s)))
    not_done = sum(1 for i in w_steps for r in range(R) if prev[r] < i)
    return Outcome(checks=checks_out, attempted=R * len(w_steps),
                   failed=not_done, results=results, begin=begin, end=end,
                   facts={"processed": processed})
