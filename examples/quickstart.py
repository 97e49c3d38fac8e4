"""Quickstart: the whole ElasticBroker-JAX loop in one small script.

Trains a tiny LM while streaming per-layer field taps through the broker to a
Cloud-style stream-processing engine running online DMD — you watch the
training dynamics' eigen-stability converge *while the job runs*, which is
the paper's whole point.  The entire HPC→Cloud deployment is one
``WorkflowConfig`` + ``Session``.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

import repro.configs as C
from repro.analysis.dmd import StreamingDMD
from repro.analysis.metrics import unit_circle_distance
from repro.core.taps import TapStreamer
from repro.data.pipeline import TokenPipeline
from repro.models import transformer as T
from repro.models.modules import materialize
from repro.models.steps import make_train_step
from repro.optim import adamw
from repro.workflow import OperatorPipeline, Session, WorkflowConfig

# ---- 1. the "HPC" side: a (tiny) LM training job --------------------------
cfg = C.get("starcoder2-3b").reduced()
params = materialize(T.build_specs(cfg), jax.random.key(0), cfg.dtype)
opt_cfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=5)
opt = adamw.init_opt_state(opt_cfg, params)
train_step = jax.jit(make_train_step(cfg, opt_cfg))
pipe = TokenPipeline(cfg, batch=8, seq=64)

# ---- 2. the "Cloud" side: one declarative workflow -------------------------
N_REGIONS = 4
workflow = WorkflowConfig(n_producers=N_REGIONS, n_groups=1,
                          executors_per_group=4, compress="int8+zstd",
                          trigger_interval=0.5)
dmd_states = {}

def analyze(key, records):
    sd = dmd_states.setdefault(
        key, StreamingDMD(n_features=cfg.tap_snapshot_dim, window=16, rank=4))
    for r in sorted(records, key=lambda r: r.step):
        sd.update(np.asarray(r.payload).reshape(-1)[: cfg.tap_snapshot_dim])
    return unit_circle_distance(sd.eigenvalues())

pipeline = OperatorPipeline(granularity="batch").map("analyze", analyze,
                                                     ordering="ordered")
session = Session(workflow, pipeline=pipeline)
streamer = TapStreamer(session, n_regions=N_REGIONS)

# ---- 3. run the cross-ecosystem workflow -----------------------------------
print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M params) "
      f"with in-situ streaming analysis...")
for step in range(30):
    params, opt, metrics, taps = train_step(params, opt, pipe.batch_at(step))
    streamer.publish(step, {"resid_norm": taps["resid_norm"],
                            "snapshot": taps["snapshot"]})
    if step % 10 == 0:
        print(f"  step {step:3d}  loss {float(metrics['loss']):.4f}")

stats = session.close()

# ---- 4. realtime insights (paper Fig 5 analog) -----------------------------
print("\nper-region DMD stability of training dynamics "
      "(closer to 0 = more stable):")
panel = {r.stream_key: r.value for r in session.results()
         if not isinstance(r.value, Exception)}
for key in sorted(panel):
    bar = "#" * int(min(panel[key], 1.0) * 40)
    print(f"  {key:28s} {panel[key]:8.5f} {bar}")
lat = session.latency_stats()
print(f"\nstream latency mean={lat['mean']*1e3:.1f}ms p99={lat['p99']*1e3:.1f}ms"
      f"  (records: {stats.sent} in {stats.frames_sent} frames, "
      f"dropped: {stats.dropped})")
