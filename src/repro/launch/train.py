"""Production training driver.

Wires every subsystem together: config registry -> sharded params/optimizer ->
jitted train step (microbatched, optionally 8-bit moments) -> deterministic
data pipeline -> async checkpointing -> broker taps streaming to the Cloud
analysis plane -> failure detector heartbeats.

On a real TPU cluster this runs one process per host under the production
mesh; on CPU (CI / examples) pass ``--preset ci`` for a reduced config.

Usage:
  python -m repro.launch.train --arch starcoder2-3b --steps 100 --preset ci
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax

from repro import configs
from repro.checkpoint.ckpt import CheckpointManager
from repro.core.taps import TapStreamer
from repro.data.pipeline import TokenPipeline
from repro.models import transformer as T
from repro.models.modules import materialize
from repro.models.steps import make_train_step
from repro.optim import adamw
from repro.runtime.fault import FailureDetector
from repro.workflow import OperatorPipeline, Session, WorkflowConfig
from repro.analysis.dmd import StreamingDMD
from repro.analysis.metrics import unit_circle_distance


def dmd_analyzer(n_features: int) -> OperatorPipeline:
    """Per-region online DMD stability, one ordered stage per micro-batch."""
    states: dict = {}

    def analyze(key, records):
        sd = states.setdefault(
            key, StreamingDMD(n_features=n_features, window=16, rank=4))
        # one device call per micro-batch (not per record)
        sd.update_batch([r.payload for r in
                         sorted(records, key=lambda r: r.step)])
        return unit_circle_distance(sd.eigenvalues())

    return OperatorPipeline(granularity="batch").map("analyze", analyze,
                                                     ordering="ordered")


def build(arch: str, preset: str, batch: int, seq: int, microbatches: int,
          mesh=None):
    cfg = configs.get(arch)
    if preset == "ci":
        cfg = cfg.reduced()
    constrain = T._ID
    if mesh is not None:
        from repro.launch.shardings import make_constrain
        constrain = make_constrain(mesh)
    params = materialize(T.build_specs(cfg), jax.random.key(0), cfg.dtype)
    opt_cfg = adamw.AdamWConfig(use_8bit=cfg.opt_8bit, lr=3e-3,
                                warmup_steps=20)
    opt = adamw.init_opt_state(opt_cfg, params)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, microbatches, constrain))
    pipe = TokenPipeline(cfg, batch=batch, seq=seq)
    return cfg, params, opt, step_fn, pipe


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="starcoder2-3b")
    p.add_argument("--preset", default="ci", choices=["ci", "full"])
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=20)
    p.add_argument("--regions", type=int, default=4)
    p.add_argument("--no-broker", action="store_true")
    p.add_argument("--resume", action="store_true")
    args = p.parse_args(argv)

    cfg, params, opt, step_fn, pipe = build(
        args.arch, args.preset, args.batch, args.seq, args.microbatches)
    mgr = CheckpointManager(Path(args.ckpt_dir) / cfg.name)

    start = 0
    if args.resume and mgr.latest_step() is not None:
        tree, start = mgr.restore({"params": params, "opt": opt})
        params, opt = tree["params"], tree["opt"]
        print(f"[train] resumed from step {start}")

    session = streamer = None
    if not args.no_broker:
        workflow = WorkflowConfig(n_producers=args.regions,
                                  n_groups=max(1, args.regions // 4),
                                  executors_per_group=4,
                                  compress="int8+zstd", trigger_interval=1.0,
                                  n_executors=args.regions)
        session = Session(workflow,
                          pipeline=dmd_analyzer(cfg.tap_snapshot_dim))
        streamer = TapStreamer(session, n_regions=args.regions)

    det = FailureDetector(timeout_s=30.0)
    det.register("trainer", "producer")

    t0 = time.time()
    for s in range(start, args.steps):
        params, opt, metrics, taps = step_fn(params, opt, pipe.batch_at(s))
        det.beat("trainer")
        if streamer is not None:
            streamer.publish(s, {"resid_norm": taps["resid_norm"],
                                 "snapshot": taps["snapshot"]})
        if (s + 1) % args.ckpt_every == 0:
            mgr.save(s + 1, {"params": params, "opt": opt})
        if s % 10 == 0 or s == args.steps - 1:
            print(f"[train] step {s} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"({(time.time()-t0)/(s-start+1):.2f}s/step)", flush=True)
    mgr.wait()

    if session is not None:
        stats = session.close()      # broker drain -> engine drain, in order
        results = session.results()
        failed = [r for r in results if isinstance(r.value, Exception)]
        panel = {r.stream_key: r.value for r in results
                 if not isinstance(r.value, Exception)}
        print("[analysis] per-region DMD stability "
              "(closer to 0 = more stable dynamics):")
        for k in sorted(panel):
            print(f"  {k:32s} {panel[k]:.5f}")
        print(f"[analysis] stream latency: {session.latency_stats()}")
        print(f"[analysis] failed analyses: {len(failed)}")
        for r in failed[:3]:
            print(f"  {r.stream_key}: {r.value!r}")
        print(f"[broker] {stats}")
        if failed:
            raise SystemExit(f"[analysis] {len(failed)} analyses failed")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
