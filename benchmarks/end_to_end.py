"""Paper Fig 6: simulation elapsed time under three I/O modes x write
intervals, plus workflow end-to-end time for the ElasticBroker mode.

Modes (paper §4.2):
  file-based      — synchronous np.save per write (the Lustre 'collated' write)
  elasticbroker   — async broker streaming to endpoints + DMD engine
  simulation-only — writes disabled

CPU-host proxy of the Karst/Jetstream run: same protocol, scaled problem.
The container has no parallel filesystem, so the file-based mode reports two
columns: ``file_raw`` (local page-cache writes — unrealistically fast) and
``file_pfs`` with an explicit shared-FS model (FS_LATENCY_S per file create +
FS_BW aggregate bandwidth; Lustre small-file latencies of 2–10 ms are
well-documented, we use the conservative low end).  The broker path gets no
such adjustment — if anything it is *penalized* here because its sender
threads share this host's single core with the simulation.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.analysis.dmd import StreamingDMD
from repro.analysis.metrics import unit_circle_distance
from repro.launch.compile_cache import enable_compile_cache
from repro.sim.cfd import CFDConfig, init_state, region_fields, step
from repro.workflow import Session, WorkflowConfig

N_STEPS = 120
INTERVALS = (5, 10, 20)
FS_LATENCY_S = 0.002          # per-file create+commit on a shared PFS
FS_BW = 500e6                 # aggregate PFS bandwidth (bytes/s)


def _make_analyzer(n_feat, batched: bool = True):
    """batched=True: one device call per micro-batch (update_batch);
    False: the seed per-record protocol, kept as the comparison baseline."""
    states = {}

    def analyze(key, records):
        sd = states.setdefault(key, StreamingDMD(n_features=n_feat, window=12,
                                                 rank=4))
        recs = sorted(records, key=lambda r: r.step)
        if batched:
            sd.update_batch([r.payload for r in recs])
        else:
            for r in recs:
                sd.update(r.payload.reshape(-1)[:n_feat])
        return unit_circle_distance(sd.eigenvalues())

    return analyze


def run_mode(mode: str, write_interval: int, cfg: CFDConfig,
             fs_model: bool = False, batched: bool = True):
    state = init_state(cfg)
    state = step(state, cfg)  # warm the jit outside the timed region
    n_feat = 256

    tmpdir = None
    session = velocity = None
    if mode == "file":
        tmpdir = Path(tempfile.mkdtemp(prefix="ebk_fig6_"))
    elif mode == "broker":
        workflow = WorkflowConfig(n_producers=cfg.n_regions,
                                  n_groups=max(1, cfg.n_regions // 4),
                                  executors_per_group=4,
                                  compress="int8+zstd",
                                  max_batch_records=32 if batched else 1,
                                  trigger_interval=0.25,
                                  n_executors=cfg.n_regions)
        session = Session(workflow,
                          analyze=_make_analyzer(n_feat, batched=batched))
        velocity = session.open_field("velocity")

    t0 = time.time()
    for s in range(N_STEPS):
        state = step(state, cfg)
        if s % write_interval == 0:
            fields = region_fields(state, cfg)
            if mode == "file":
                for r, f in enumerate(fields):
                    np.save(tmpdir / f"step{s}_r{r}.npy", f)
                    (tmpdir / f"step{s}_r{r}.npy").stat()
                    if fs_model:  # shared-PFS create latency + bandwidth
                        time.sleep(FS_LATENCY_S + f.nbytes / FS_BW)
            elif mode == "broker":
                for r, f in enumerate(fields):
                    velocity.write(s, f, rank=r)
    np.asarray(state["u"]).sum()  # block on device work
    sim_elapsed = time.time() - t0

    e2e = None
    if mode == "broker":
        session.flush()
        session.close()
        results = session.results()
        if results:
            e2e = max(r.t_analyzed for r in results) - t0
    if tmpdir:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return sim_elapsed, e2e


def main(csv=True):
    cfg = CFDConfig(nx=192, nz=96, n_regions=16, pressure_iters=50)
    rows = []
    for interval in INTERVALS:
        times = {}
        e2e_t = None
        for mode, kw in (("simonly", {}), ("file_raw", {}),
                         ("file_pfs", {"fs_model": True}), ("broker", {}),
                         ("broker_rec", {"batched": False})):
            base = {"simonly": "none", "file_raw": "file",
                    "file_pfs": "file", "broker": "broker",
                    "broker_rec": "broker"}[mode]
            t, e2e = run_mode(base, interval, cfg, **kw)
            times[mode] = t
            if e2e and mode == "broker":
                e2e_t = e2e
        rows.append((interval, times["simonly"], times["file_raw"],
                     times["file_pfs"], times["broker"], times["broker_rec"],
                     e2e_t or float("nan")))
    if csv:
        print("fig6_interval,simonly_s,file_raw_s,file_pfs_s,broker_s,"
              "broker_perrecord_s,workflow_e2e_s")
        for r in rows:
            print(",".join(f"{v:.3f}" if isinstance(v, float) else str(v)
                           for v in r))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
