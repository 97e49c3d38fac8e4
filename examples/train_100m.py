"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps with
the full production stack — microbatched train step, async checkpointing with
mid-run restore, and broker-streamed DMD telemetry.

    PYTHONPATH=src python examples/train_100m.py            # full (~100M)
    PYTHONPATH=src python examples/train_100m.py --ci       # CPU-CI scale
"""
import argparse
import time
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np

import repro.configs as C
from repro.checkpoint.ckpt import CheckpointManager
from repro.core.api import broker_connect
from repro.core.broker import BrokerConfig
from repro.core.grouping import GroupPlan
from repro.core.taps import TapStreamer
from repro.data.pipeline import TokenPipeline
from repro.launch.train import dmd_analyzer
from repro.models import transformer as T
from repro.models.modules import materialize
from repro.models.steps import make_train_step
from repro.optim import adamw
from repro.streaming.endpoint import make_endpoints
from repro.streaming.engine import StreamEngine

p = argparse.ArgumentParser()
p.add_argument("--ci", action="store_true", help="CPU-CI scale (~8M, 40 steps)")
p.add_argument("--steps", type=int, default=None)
args = p.parse_args()

base = C.get("starcoder2-3b")
if args.ci:
    cfg = replace(base.reduced(), name="sc2-8m", d_model=256, n_layers=4,
                  d_ff=1024, vocab_size=2048, n_heads=8, n_kv_heads=2,
                  head_dim=32)
    steps, batch, seq, mb = args.steps or 40, 8, 128, 1
else:
    cfg = replace(base, name="sc2-100m", d_model=768, n_layers=12,
                  d_ff=3072, n_heads=12, n_kv_heads=2, head_dim=64,
                  vocab_size=32768, dtype=jax.numpy.float32, remat=False)
    steps, batch, seq, mb = args.steps or 300, 16, 512, 2

params = materialize(T.build_specs(cfg), jax.random.key(0), cfg.dtype)
n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
print(f"model: {cfg.name}, {n_params/1e6:.1f}M params, {steps} steps")

opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=max(10, steps // 10),
                            total_steps=steps)
opt = adamw.init_opt_state(opt_cfg, params)
step_fn = jax.jit(make_train_step(cfg, opt_cfg, mb))
pipe = TokenPipeline(cfg, batch=batch, seq=seq)
mgr = CheckpointManager(Path("/tmp/repro_ckpt") / cfg.name, keep=2)

# broker + cloud analysis plane
N_REGIONS = 4
eps = make_endpoints(1)
broker = broker_connect(eps, n_producers=N_REGIONS,
                        cfg=BrokerConfig(compress="int8+zstd"),
                        plan=GroupPlan(N_REGIONS, 1, 4))
engine = StreamEngine([e.handle for e in eps],
                      dmd_analyzer(cfg.tap_snapshot_dim).compile(),
                      n_executors=4, trigger_interval=1.0)
streamer = TapStreamer(broker, n_regions=N_REGIONS)

losses = []
t0 = time.time()
for s in range(steps):
    params, opt, metrics, taps = step_fn(params, opt, pipe.batch_at(s))
    losses.append(float(metrics["loss"]))
    streamer.publish(s, {"resid_norm": taps["resid_norm"],
                         "snapshot": taps["snapshot"]})
    if (s + 1) % 20 == 0:
        mgr.save(s + 1, {"params": params, "opt": opt})   # async
    if s % max(1, steps // 10) == 0:
        dt = (time.time() - t0) / (s + 1)
        print(f"  step {s:4d} loss {losses[-1]:.4f}  {dt:.2f}s/step")
mgr.wait()

# demonstrate checkpoint restore mid-history
restored, rstep = mgr.restore({"params": params, "opt": opt})
print(f"restored checkpoint from step {rstep} "
      f"({mgr.save_count} checkpoints written)")

broker.flush()
engine.drain_and_stop()
panel = {r.stream_key: r.value for r in engine.collect()
         if not isinstance(r.value, Exception)}
print(f"\nfinal loss {losses[-1]:.4f} (start {losses[0]:.4f}) — "
      f"{'LEARNING' if losses[-1] < losses[0] * 0.8 else 'check hyperparams'}")
print("DMD stability by region:",
      {k.split('/')[0] + '/' + k.split('/')[-1]: round(v, 4)
       for k, v in sorted(panel.items())})
assert losses[-1] < losses[0], "training must reduce loss"
