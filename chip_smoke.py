#!/usr/bin/env python3
"""Chip smoke test: the paper's CFD -> broker -> DMD workflow on one TPU.

Runs the paper's Fig 6 deployment once, end to end, through
the entry points a user calls (``Session``, ``OperatorPipeline``,
``StreamingDMD``, ``batched_window_dmd``):

* a 192x96 wind-around-buildings CFD run split into 16 z-slab producers,
  200 steps writing every 5th.  Each record is a whole slab: 2304 floats
  (6 rows x 192 x 2 velocity components);
* 4 broker groups shipping int8+zstd frames through the Pallas codec.  The
  producer hands the broker 8 writes at a time, so each group's frame
  carries 32 slabs = 288 codec blocks (a multi-step kernel grid);
* per-region ``StreamingDMD``: G and A are 2 x 2304² f32 per region on the
  device, updated by the Pallas ``gram_pair`` kernel.  The run crosses the
  snapshot window, so both ``exact_dmd`` and ``gram_eigs`` run;
* a keyed tumbling window into ``BatchAggregate(make_dmd_aggregate(...))``,
  so ``batched_window_dmd`` solves co-fired 2304-feature panes.

The producer waits for each hand-off to be analyzed before the next one
(the wait is not counted in its step time), which fixes every shape the
device sees; all of them are compiled first and timed as set-up.

Checks: no analysis failed; every region and sink produced results; the
broker dropped nothing and sent every record written; the Pallas codec
was taken with frames of more than 256 blocks; every eigenvalue set, from
each region and each window pane, matches a plain numpy reference (thin
SVD + ``np.linalg.eigvals`` in float64 over the same snapshots, int8 wire
round trip included) within ``EIG_TOL``.  Prints the device, compile
seconds per phase, step time with the broker off and on, wire counts, the
largest eigenvalue error and peak device memory; the last line is
``{"ok": true, "device": {...}}``.  Exits nonzero, with no such line, when
JAX finds no TPU or any check fails.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_STEPS = 200
WRITE_EVERY = 5
BURST = 8              # writes per broker hand-off (32 records per group)
RANK = 4
WINDOW = 16            # StreamingDMD window: hand-offs 1-2 exact, 3-5 Gram
PANE_BURSTS = 4        # tumbling panes of 4 hand-offs = 32 snapshots
EIG_TOL = 1e-2         # abs. eigenvalue error vs the float64 reference
REL_TOL = 1e-4         # the DMD routes' s² cutoff (analysis.dmd)
QBLOCK = 256           # wire codec block (core.records)
WAIT_S = 900.0


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def int8_roundtrip(x: np.ndarray) -> np.ndarray:
    """The int8 wire codec, numpy only: per record, blocks of QBLOCK with
    scale max|block| * (1/127) in f32, round half to even, clip to ±127."""
    n, d = x.shape
    nb = -(-d // QBLOCK)
    b = np.pad(x.astype(np.float32), ((0, 0), (0, nb * QBLOCK - d)))
    b = b.reshape(n * nb, QBLOCK)
    scale = (np.maximum(np.abs(b).max(axis=1), np.float32(1e-20))
             * np.float32(1.0 / 127.0))
    q = np.clip(np.round(b / scale[:, None]), -127, 127)
    return (q * scale[:, None]).reshape(n, nb * QBLOCK)[:, :d]


def reference_eigs(snaps: np.ndarray, k: int):
    """Exact DMD truncated to k directions, float64: X = snaps[:-1]ᵀ,
    Y = snaps[1:]ᵀ, X = U S Vᵀ, A~ = U_kᵀ Y V_k S_k⁻¹, eig(A~)."""
    X = snaps[:-1].T.astype(np.float64)
    Y = snaps[1:].T.astype(np.float64)
    U, s, Vt = np.linalg.svd(X, full_matrices=False)
    At = (U[:, :k].T @ Y @ Vt[:k].T) / s[:k][None, :]
    return np.linalg.eigvals(At), s


def compare(got: np.ndarray, snaps: np.ndarray, route: str) -> float:
    """Largest error between the system's finite eigenvalues and the
    reference at the same truncation, matched nearest-first.  The
    system's truncation must agree with the reference's own rule to
    within one direction (a direction at the cutoff may fall either
    way in f32)."""
    got = got[np.isfinite(got)]
    check(got.size >= 1, f"{route}: no finite eigenvalues")
    want, s = reference_eigs(snaps, got.size)
    r = min(RANK, s.size)
    k_ref = int((s[:r] ** 2 > REL_TOL * s[0] ** 2).sum())
    check(abs(got.size - k_ref) <= 1,
          f"{route}: kept {got.size} directions, reference rule keeps "
          f"{k_ref}")
    pool = list(got)
    err = 0.0
    for w in want[np.argsort(-np.abs(want))]:
        j = int(np.argmin([abs(g - w) for g in pool]))
        err = max(err, float(abs(pool.pop(j) - w)))
    return err


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def smoke(cfg) -> dict:
    """Run the deployment on ``cfg`` and check it; returns the numbers."""
    import jax

    from repro.analysis.dmd import (StreamingDMD, batched_window_dmd,
                                    make_dmd_aggregate)
    from repro.core import records
    from repro.sim.cfd import init_state, region_fields, step
    from repro.workflow import OperatorPipeline, Session, WorkflowConfig

    d = 2 * (cfg.nz // cfg.n_regions) * cfg.nx      # one slab, u and w
    rng = np.random.RandomState(0)
    out: dict = {"n_features": d}

    # ---- set-up: compile every program the run uses, at its shapes ------
    compile_s = {}
    compile_s["cfd_step"] = _timed(
        lambda: jax.block_until_ready(step(init_state(cfg), cfg)))
    check(records._pallas_rows_active(),
          "the Pallas codec is not the active rows codec on this device")
    warm = [records.StreamRecord("warm", 0, r, 0,
                                 rng.randn(d).astype(np.float32))
            for r in range(4 * BURST)]
    frames = []

    def codec_roundtrip():
        frames.append(records.encode_batch(warm, compress="int8+zstd"))
        records.decode_batch(frames[-1])

    compile_s["codec"] = _timed(codec_roundtrip)
    prev = records.set_quant_backend("numpy")
    out["codec_bytes_match_numpy"] = \
        records.encode_batch(warm, compress="int8+zstd") == frames[0]
    records.set_quant_backend(prev)
    sd = StreamingDMD(n_features=d, window=WINDOW, rank=RANK)
    for name in ("gram_pair+exact_dmd_8", "exact_dmd_16", "gram_eigs"):
        compile_s[name] = _timed(lambda: (sd.update_batch(
            rng.randn(BURST, d).astype(np.float32)), sd.eigenvalues()))
    del sd
    for m in (BURST * PANE_BURSTS, BURST):
        panes = [rng.randn(m, d).astype(np.float32)] * cfg.n_regions
        compile_s[f"window_dmd_{m}"] = _timed(
            lambda: batched_window_dmd(panes, rank=RANK, n_features=d))
    out["compile_s"] = compile_s
    print("compile_s " + " ".join(f"{k}={v:.3f}"
                                  for k, v in compile_s.items()), flush=True)

    # ---- simulation alone ----------------------------------------------
    state = jax.block_until_ready(init_state(cfg))
    t0 = time.perf_counter()
    for _ in range(N_STEPS):
        state = step(state, cfg)
    jax.block_until_ready(state)
    out["step_ms_broker_off"] = (time.perf_counter() - t0) / N_STEPS * 1e3

    # ---- simulation with the broker and both analyses ---------------------
    streaming: dict = {}

    def stream_stage(key, batch):
        sd = streaming.setdefault(key, StreamingDMD(n_features=d,
                                                    window=WINDOW, rank=RANK))
        recs = sorted(batch, key=lambda r: r.step)
        sd.update_batch(np.stack([r.payload for r in recs]))
        route = "exact_dmd" if sd.n_seen <= sd.window else "gram_eigs"
        return {"n_seen": sd.n_seen, "last_step": recs[-1].step,
                "route": route, "eigs": sd.eigenvalues()}

    def pane_records(values):       # a pane holds micro-batch record lists
        return sorted((r for batch in values for r in batch),
                      key=lambda r: r.step)

    window_dmd = make_dmd_aggregate(
        rank=RANK, n_features=d,
        prepare=lambda values: [r.payload for r in pane_records(values)])

    def window_stage(items):
        eigs = window_dmd(items)
        return [{"steps": [r.step for r in pane_records(values)], "eigs": e}
                for (_key, values), e in zip(items, eigs)]

    pane_s = PANE_BURSTS * BURST * WRITE_EVERY * cfg.dt
    pipeline = (OperatorPipeline(granularity="batch")
                .key_by("region", lambda key, batch: key)
                .map("streaming_dmd", stream_stage, ordering="ordered")
                .sink("streaming_eigs")
                .tumbling_window("panes", size_s=pane_s, after="region")
                .batch_aggregate("window_dmd", window_stage)
                .sink("window_eigs"))
    workflow = WorkflowConfig(n_producers=cfg.n_regions,
                              n_groups=max(1, cfg.n_regions // 4),
                              executors_per_group=4, compress="int8+zstd",
                              max_batch_records=32, trigger_interval=0.25,
                              n_executors=cfg.n_regions)
    session = Session(workflow, pipeline=pipeline)
    try:
        velocity = session.open_field("velocity", shape=(d,))
        host: dict = {}              # (region, step) -> slab as written
        staged: list = []
        written = 0
        produce_s = wait_s = 0.0

        def analyzed() -> int:
            return sum(r.n_records for r in session.results())

        state = jax.block_until_ready(init_state(cfg))
        t0 = time.perf_counter()
        for s in range(N_STEPS):
            state = step(state, cfg)
            if s % WRITE_EVERY:
                continue
            fields = region_fields(state, cfg)
            for r, f in enumerate(fields):
                host[(r, s)] = f
            staged.append((s, fields))
            if len(staged) < BURST:
                continue
            steps = [st for st, fs in staged for _ in fs]
            ranks = [r for _st, fs in staged for r in range(len(fs))]
            slabs = [f for _st, fs in staged for f in fs]
            staged = []
            # event time = simulation time, so pane membership is exact
            accepted = velocity.write_batch(steps, slabs, ranks=ranks,
                                            t=s * cfg.dt)
            check(accepted == len(slabs),
                  f"broker accepted {accepted} of {len(slabs)} records")
            written += len(slabs)
            produce_s += time.perf_counter() - t0
            t1 = time.perf_counter()
            while analyzed() < written:
                check(time.perf_counter() - t1 < WAIT_S,
                      f"only {analyzed()} of {written} records analyzed "
                      f"after {WAIT_S:.0f}s")
                time.sleep(0.02)
            wait_s += time.perf_counter() - t1
            t0 = time.perf_counter()
        jax.block_until_ready(state)
        produce_s += time.perf_counter() - t0
    finally:
        stats = session.close()      # fires the last panes (drain)
    out["step_ms_broker_on"] = produce_s / N_STEPS * 1e3
    out["analysis_wait_s"] = wait_s
    print(f"step_ms broker_off={out['step_ms_broker_off']:.4f} "
          f"broker_on={out['step_ms_broker_on']:.4f} "
          f"(analysis waits excluded: {wait_s:.3f}s)", flush=True)

    # ---- checks -----------------------------------------------------------
    results = session.results()
    failed = [r for r in results if isinstance(r.value, Exception)]
    out["failed_analyses"] = len(failed)
    print(f"failed_analyses {len(failed)}", flush=True)
    for r in failed[:3]:
        print(f"  {r.stream_key}: {r.value!r}", file=sys.stderr)
    check(not failed, f"{len(failed)} analyses failed")

    blocks = -(-d // QBLOCK) * stats.sent / max(stats.frames_sent, 1)
    out.update(records_written=written, records_sent=stats.sent,
               frames=stats.frames_sent, wire_bytes=stats.bytes_sent,
               dropped=stats.dropped, mean_blocks_per_frame=blocks)
    print(f"wire records_written={written} records_sent={stats.sent} "
          f"frames={stats.frames_sent} bytes={stats.bytes_sent} "
          f"dropped={stats.dropped} mean_blocks_per_frame={blocks:.1f} "
          f"codec_bytes_match_numpy={out['codec_bytes_match_numpy']}",
          flush=True)
    check(stats.dropped == 0, f"broker dropped {stats.dropped} records")
    check(stats.written == stats.sent == written == sum(
        r.n_records for r in results),
        f"written {stats.written}, sent {stats.sent}, produced {written}, "
        f"analyzed {sum(r.n_records for r in results)}")
    # frames never exceed max_batch_records, so a mean above 256 blocks
    # means at least one frame above 256 blocks
    check(blocks > 256, f"no frame exceeded 256 codec blocks ({blocks:.1f})")
    check(session.engine.metrics()["order_timeouts"] == 0,
          "a stream's micro-batches ran out of order")
    acct = session.exec_plan.accounting()
    check(acct["closed"] and acct["windows"]["panes"]["late_dropped"] == 0,
          f"window loss ledger: {acct}")

    def region_of(key: str) -> int:
        return int(key.rsplit("/r", 1)[1])

    stream_out = session.results("streaming_eigs")
    window_out = session.results("window_eigs")
    regions = set(range(cfg.n_regions))
    check({region_of(k) for k, _v, _t in stream_out} == regions,
          "a region produced no StreamingDMD result")
    n_panes = -(-N_STEPS // (WRITE_EVERY * BURST * PANE_BURSTS))
    check(len(window_out) == n_panes * cfg.n_regions
          and {region_of(k) for k, _v, _t in window_out} == regions,
          f"{len(window_out)} window results, want "
          f"{n_panes * cfg.n_regions}")
    routes = {v["route"] for _k, v, _t in stream_out}
    check(routes == {"exact_dmd", "gram_eigs"},
          f"StreamingDMD routes reached: {sorted(routes)}")

    def snapshots(region: int, steps) -> np.ndarray:
        return int8_roundtrip(np.stack([host[(region, s)] for s in steps]))

    errors = {"exact_dmd": 0.0, "gram_eigs": 0.0, "window_dmd": 0.0}
    write_steps = list(range(0, N_STEPS, WRITE_EVERY))
    for key, v, _t in stream_out:
        steps = write_steps[: v["n_seen"]]
        check(steps[-1] == v["last_step"],
              f"{key}: updates out of step order")
        err = compare(v["eigs"], snapshots(region_of(key), steps),
                      v["route"])
        errors[v["route"]] = max(errors[v["route"]], err)
    for key, v, _t in window_out:
        err = compare(v["eigs"], snapshots(region_of(key), v["steps"]),
                      "window_dmd")
        errors["window_dmd"] = max(errors["window_dmd"], err)
    out["eig_max_err"] = errors
    print("eig_max_err " + " ".join(f"{k}={v:.3e}" for k, v in errors.items())
          + f" tol={EIG_TOL:g} (streaming results {len(stream_out)}, "
          f"window panes {len(window_out)})", flush=True)
    check(max(errors.values()) <= EIG_TOL,
          f"eigenvalues off the numpy reference by {max(errors.values())}")
    return out


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this smoke runs only on a TPU", file=sys.stderr)
        return 2
    print(f"device platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} cache={cache}",
          flush=True)

    from repro.sim.cfd import CFDConfig
    # the paper's Fig 6 deployment
    cfg = CFDConfig(nx=192, nz=96, n_regions=16, pressure_iters=50)
    t0 = time.perf_counter()
    try:
        smoke(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}"
          f" wall_s {time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
