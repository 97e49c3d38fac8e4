"""Paper Fig 7: latency + aggregated throughput vs producer count.

Synthetic generators at the paper's producer:endpoint:executor ratio
(16:1:16 there; a CPU-host-scaled 4:1:4 here, same protocol).  Latency =
record generated -> analyzed (Fig 7a); throughput = aggregated payload
bytes/s over the run (Fig 7b).
"""
from __future__ import annotations

import time

from repro.analysis.dmd import StreamingDMD
from repro.analysis.metrics import unit_circle_distance
from repro.launch.compile_cache import enable_compile_cache
from repro.sim.synthetic import GeneratorConfig, SyntheticGenerator
from repro.workflow import Session, WorkflowConfig

RATIO = 4                     # producers per endpoint (paper: 16)
SCALES = (4, 8, 16, 32)       # paper: 16..128


def _analyzer(n_feat):
    states = {}

    def analyze(key, records):
        sd = states.setdefault(key, StreamingDMD(n_features=n_feat,
                                                 window=8, rank=3))
        sd.update_batch([r.payload for r in
                         sorted(records, key=lambda r: r.step)])
        return unit_circle_distance(sd.eigenvalues())

    return analyze


def run_scale(n_producers: int, *, steps: int = 40, rate_hz: float = 20.0,
              field_elems: int = 1024):
    n_eps = max(1, n_producers // RATIO)
    workflow = WorkflowConfig(n_producers=n_producers, n_groups=n_eps,
                              executors_per_group=RATIO,
                              compress="int8+zstd", queue_capacity=1024,
                              backpressure="block", trigger_interval=0.25)
    session = Session(workflow, analyze=_analyzer(128))
    gen = SyntheticGenerator(
        GeneratorConfig(n_producers=n_producers, field_elems=field_elems,
                        rate_hz=rate_hz, n_steps=steps), session)
    t0 = time.time()
    gen.run(wait=True)
    session.flush(timeout=30)
    session.close()
    wall = time.time() - t0
    stats = session.latency_stats()
    payload_bytes = gen.produced * field_elems * 4
    return {
        "producers": n_producers,
        "endpoints": n_eps,
        "executors": session.plan.n_executors,
        "records": gen.produced,
        "dropped": session.stats.dropped,
        "latency_mean_s": stats.get("mean", float("nan")),
        "latency_p99_s": stats.get("p99", float("nan")),
        "throughput_MBps": payload_bytes / wall / 1e6,
        "throughput_rec_s": gen.produced / wall,
    }


def main(csv=True):
    rows = [run_scale(n) for n in SCALES]
    if csv:
        print("fig7_producers,endpoints,executors,records,dropped,"
              "latency_mean_s,latency_p99_s,throughput_MBps,throughput_rec_s")
        for r in rows:
            print(f"{r['producers']},{r['endpoints']},{r['executors']},"
                  f"{r['records']},{r['dropped']},{r['latency_mean_s']:.3f},"
                  f"{r['latency_p99_s']:.3f},{r['throughput_MBps']:.2f},"
                  f"{r['throughput_rec_s']:.1f}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
