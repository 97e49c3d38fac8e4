"""End-to-end cross-ecosystem workflows — the paper's two experiments,
miniaturized: (1) CFD -> broker -> endpoints -> stream engine -> DMD
stability panel (Fig 4/5); (2) LM training with in-graph taps streamed to
online DMD (the TPU-native adaptation)."""
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.analysis.dmd import StreamingDMD
from repro.analysis.metrics import unit_circle_distance
from repro.core.api import broker_connect, broker_init, broker_write
from repro.core.broker import BrokerConfig
from repro.core.grouping import GroupPlan
from repro.core.taps import TapStreamer
from repro.data.pipeline import TokenPipeline
from repro.models import transformer as T
from repro.models.modules import materialize
from repro.models.steps import make_train_step
from repro.optim import adamw
from repro.sim.cfd import CFDConfig, init_state, region_fields, step
from repro.streaming.endpoint import make_endpoints
from repro.streaming.engine import StreamEngine

from one_stage import one_stage


def _dmd_analyzer(n_features):
    states = {}

    def analyze(key, records):
        sd = states.setdefault(key, StreamingDMD(n_features=n_features,
                                                 window=12, rank=4))
        for r in sorted(records, key=lambda r: r.step):
            sd.update(r.payload.reshape(-1)[:n_features])
        return unit_circle_distance(sd.eigenvalues())

    return analyze


def test_cfd_insitu_workflow():
    cfg = CFDConfig(nx=48, nz=16, n_regions=4, pressure_iters=40)
    n_feat = 64
    eps = make_endpoints(2)
    broker = broker_connect(eps, n_producers=cfg.n_regions,
                            cfg=BrokerConfig(compress="int8+zstd"),
                            plan=GroupPlan(cfg.n_regions, 2, 2))
    engine = StreamEngine([e.handle for e in eps],
                          one_stage(_dmd_analyzer(n_feat)).compile(),
                          n_executors=4, trigger_interval=0.05)
    ctxs = [broker_init("velocity", r) for r in range(cfg.n_regions)]

    state = init_state(cfg)
    for s in range(25):
        state = step(state, cfg)
        if s % 2 == 0:  # write_interval=2
            for r, field in enumerate(region_fields(state, cfg)):
                broker_write(ctxs[r], s, field[:n_feat])
    broker.flush()
    engine.drain_and_stop()

    results = engine.collect()
    assert results, "no analysis results reached the collector"
    by_region = {}
    for r in results:
        if not isinstance(r.value, Exception):
            by_region[r.stream_key] = r.value
    assert len(by_region) == cfg.n_regions
    assert all(np.isfinite(v) for v in by_region.values())
    stats = engine.latency_stats()
    assert stats["mean"] < 5.0        # in-time insight, not post-hoc
    assert broker.stats.dropped == 0


def test_training_tap_workflow():
    """The TPU-native ElasticBroker: train-step taps -> broker -> DMD."""
    cfg = C.get("minitron-8b").reduced()
    params = materialize(T.build_specs(cfg), jax.random.key(0), jnp.float32)
    opt_cfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=2)
    opt = adamw.init_opt_state(opt_cfg, params)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, 1))
    pipe = TokenPipeline(cfg, batch=4, seq=32)

    n_regions = 4
    eps = make_endpoints(2)
    broker = broker_connect(eps, n_producers=n_regions,
                            cfg=BrokerConfig(compress="none"),
                            plan=GroupPlan(n_regions, 2, 2))
    streamer = TapStreamer(broker, n_regions=n_regions)
    engine = StreamEngine([e.handle for e in eps],
                          one_stage(_dmd_analyzer(cfg.tap_snapshot_dim))
                          .compile(),
                          n_executors=2, trigger_interval=0.05)

    losses = []
    for s in range(12):
        params, opt, metrics, taps = step_fn(params, opt, pipe.batch_at(s))
        losses.append(float(metrics["loss"]))
        streamer.publish(s, {"resid_norm": taps["resid_norm"],
                             "snapshot": taps["snapshot"]})
    broker.flush()
    engine.drain_and_stop()

    assert losses[-1] < losses[0], "training should reduce loss on markov data"
    results = [r for r in engine.collect() if not isinstance(r.value, Exception)]
    assert results
    keys = {r.stream_key for r in results}
    # 2 fields x 4 regions
    assert len(keys) == 2 * n_regions
    assert broker.stats.sent > 0
