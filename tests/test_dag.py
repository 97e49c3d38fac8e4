"""In-situ analysis DAGs (paper §6 future work): multi-stage operator
graphs running inside the stream engine, with filtering alert sinks."""
import numpy as np

from repro.analysis.dmd import StreamingDMD
from repro.analysis.metrics import unit_circle_distance
from repro.core.broker import Broker, BrokerConfig
from repro.core.grouping import GroupPlan
from repro.streaming.endpoint import make_endpoints
from repro.streaming.engine import StreamEngine
from repro.streaming.operators import OperatorPipeline


def test_dag_in_engine_with_alerting():
    dmd_states = {}

    def dmd_stage(key, records):
        sd = dmd_states.setdefault(key, StreamingDMD(n_features=16, window=8,
                                                     rank=3))
        for r in sorted(records, key=lambda r: r.step):
            sd.update(r.payload.reshape(-1)[:16])
        return sd.eigenvalues()

    def stability_stage(key, eigs):
        return unit_circle_distance(eigs)

    alerts = []

    def alert_stage(key, score):
        # decaying stream (|lambda| = 0.55, score 0.2025) => far from the
        # unit circle; the neutral rotation scores ~0
        if score > 0.1:
            return ("UNSTABLE", key, score)
        return None                   # filtered: no sink entry, no fan-out

    plan = (OperatorPipeline(granularity="batch")
            .map("dmd", dmd_stage)
            .map("stability", stability_stage).sink("stability_out")
            .map("alert", alert_stage).sink("alerts")
            .compile())

    eps = make_endpoints(1)
    broker = Broker(GroupPlan(2, 1, 2), eps, BrokerConfig(compress="none"))
    engine = StreamEngine([e.handle for e in eps], plan, n_executors=2,
                          trigger_interval=0.05)

    # stream 0: strongly decaying (unstable score); stream 1: neutral rotation
    rng = np.random.RandomState(0)
    mix = np.linalg.qr(rng.randn(16, 2))[0]
    for step in range(30):
        z_dec = 0.55 ** step
        broker.write("f", 0, step, (mix[:, 0] * z_dec).astype(np.float32))
        ang = 0.3 * step
        z_rot = np.array([np.cos(ang), np.sin(ang)])
        broker.write("f", 1, step, (mix @ z_rot).astype(np.float32))
    broker.flush()
    engine.drain_and_stop()

    stab = {k: v for k, v, _ in plan.results("stability_out")}
    assert len(stab) == 2
    unstable_keys = {k for k, v, _ in plan.results("alerts")}
    assert any("r0" in k for k in unstable_keys)     # decaying stream alerted
    assert not any("r1" in k for k in unstable_keys) # rotation is neutral
