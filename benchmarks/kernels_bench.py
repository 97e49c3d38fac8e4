"""Kernel-layer microbenchmarks (CPU-host: wall time for the portable jnp
paths + host codec; the Pallas kernels are interpret-validated, their TPU
performance is captured structurally in the §Roofline VMEM analysis).

``bench_hotpath`` is the broker→DMD hot-path scoreboard: it times the seed
per-snapshot ``StreamingDMD`` protocol against the batched ``update_batch``
path (counting host↔device transfers and device calls via the instance
counters) and single-record ``encode`` against ``encode_batch``, then
writes ``BENCH_hotpath.json`` at the repo root so the trajectory is tracked
PR over PR.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.dmd import StreamingDMD, batched_window_dmd, window_dmd
from repro.core.records import StreamRecord, encode, decode, encode_batch, \
    decode_batch
from repro.kernels import ref
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import flash_attention

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_hotpath.json"
MULTIKEY_JSON = Path(__file__).resolve().parents[1] / "BENCH_multikey.json"


def _time(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))   # compile/warm
    t0 = time.time()
    for _ in range(reps):
        # block every rep: async backends otherwise queue all reps and only
        # the last one is awaited, under-reporting per-call latency
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6  # us


def bench_attention():
    rng = np.random.RandomState(0)
    B, S, H, D, Kh = 1, 1024, 8, 64, 2
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Kh, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Kh, D), jnp.float32)
    ke, ve = jnp.repeat(k, H // Kh, 2), jnp.repeat(v, H // Kh, 2)
    naive = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                    chunk=256))
    t_naive = _time(naive, q, ke, ve)
    t_flash = _time(flash, q, k, v)
    flops = 4 * B * S * S * H * D
    return [("attention_naive_1k", t_naive, f"{flops/t_naive/1e3:.1f}GF/s"),
            ("attention_flash_jnp_1k", t_flash, f"{flops/t_flash/1e3:.1f}GF/s")]


def bench_gram():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(512, 256), jnp.float32)
    y = jnp.asarray(rng.randn(512, 256), jnp.float32)
    g = jnp.zeros((256, 256), jnp.float32)
    a = jnp.zeros((256, 256), jnp.float32)
    f = jax.jit(lambda x, g: ref.gram_ref(x, g))
    fp = jax.jit(lambda x, y, g, a: ref.gram_pair_ref(x, y, g, a))
    t = _time(f, x, g)
    tp = _time(fp, x, y, g, a)
    flops = 2 * 512 * 256 * 256
    return [("gram_update_512x256", t, f"{flops/t/1e3:.1f}GF/s"),
            ("gram_pair_fused_512x256", tp, f"{2*flops/tp/1e3:.1f}GF/s")]


def bench_codec():
    rng = np.random.RandomState(0)
    payload = rng.randn(4096).astype(np.float32)
    rec = StreamRecord("f", 0, 0, 0, payload)
    out = []
    for comp in ("none", "zstd", "int8", "int8+zstd"):
        blob = encode(rec, compress=comp)
        t0 = time.time()
        n = 200
        for _ in range(n):
            decode(encode(rec, compress=comp))
        us = (time.time() - t0) / n * 1e6
        out.append((f"record_codec_{comp}", us,
                    f"{len(blob)}B/rec {4096*4/len(blob):.1f}x"))
    return out


def bench_ssd():
    rng = np.random.RandomState(0)
    from repro.models.mamba import ssd_chunked
    B, S, H, P, N = 1, 512, 4, 16, 32
    xh = jnp.asarray(rng.randn(B, S, H, P), jnp.float32)
    dt = jnp.abs(jnp.asarray(rng.randn(B, S, H), jnp.float32)) * 0.1
    A = -jnp.abs(jnp.asarray(rng.randn(H), jnp.float32))
    Bm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    f = jax.jit(lambda *a: ssd_chunked(*a, chunk=128)[0])
    t = _time(f, xh, dt, A, Bm, Cm)
    flops = 2 * B * S * 128 * (N + H * P)  # CB + masked matmul approx
    return [("ssd_chunked_512", t, f"{flops/t/1e3:.1f}GF/s")]


def bench_dmd():
    rng = np.random.RandomState(0)
    sd = StreamingDMD(n_features=128, window=16, rank=4)
    for i in range(20):
        sd.update(rng.randn(128).astype(np.float32))
    t0 = time.time()
    n = 20
    for i in range(n):
        sd.update(rng.randn(128).astype(np.float32))
        sd.eigenvalues()
    us = (time.time() - t0) / n * 1e6
    sb = StreamingDMD(n_features=128, window=16, rank=4)
    batch = [rng.randn(128).astype(np.float32) for _ in range(20)]
    sb.update_batch(batch)        # warm
    sb.eigenvalues()
    t0 = time.time()
    sb.update_batch(batch)
    sb.eigenvalues()
    us_b = (time.time() - t0) / n * 1e6
    return [("streaming_dmd_update+eigs_128", us, "per-snapshot"),
            ("streaming_dmd_batched_128", us_b, "per-snapshot, batch=20")]


def _run_dmd_protocol(snaps, batch: int | None, eigs: bool = True,
                      donate: bool = True):
    """Run the update(+eigenvalues) protocol; returns (wall_s, counters).

    eigs=False isolates the update path: the full protocol also runs 16x
    fewer eigen-solves in batched mode (one per micro-batch instead of one
    per record), so the update-only numbers are what attribute the win to
    transfer/dispatch batching alone."""
    d = snaps.shape[1]
    sd = StreamingDMD(n_features=d, window=16, rank=4, donate=donate)
    t0 = time.time()
    if batch is None:              # seed protocol: one device round per record
        for s in snaps:
            sd.update(s)
            if eigs:
                sd.eigenvalues()
    else:                          # batched protocol: one round per micro-batch
        for i in range(0, len(snaps), batch):
            sd.update_batch(snaps[i: i + batch])
            if eigs:
                sd.eigenvalues()
    wall = time.time() - t0
    return wall, {"h2d": sd.h2d_transfers, "d2h": sd.d2h_transfers,
                  "device_calls": sd.device_calls}


def bench_hotpath(write_json: bool = True):
    """Batched-vs-unbatched scoreboard for the two hot paths."""
    rng = np.random.RandomState(0)
    d, total, batch = 128, 64, 16
    snaps = rng.randn(total, d).astype(np.float32)
    _run_dmd_protocol(snaps, None)        # warm jit for both protocols
    _run_dmd_protocol(snaps, batch)
    wall_seq, c_seq = _run_dmd_protocol(snaps, None)
    wall_bat, c_bat = _run_dmd_protocol(snaps, batch)
    t_seq = sum(c_seq.values()) - c_seq["device_calls"]
    t_bat = sum(c_bat.values()) - c_bat["device_calls"]
    # update-only: isolates transfer/dispatch batching from the eigen-solve
    # cadence (the full protocol also amortizes eigenvalues() per batch)
    wall_useq, c_useq = _run_dmd_protocol(snaps, None, eigs=False)
    wall_ubat, c_ubat = _run_dmd_protocol(snaps, batch, eigs=False)

    # d=512 update-only: donation + the no-copy block path at the width the
    # paper's field snapshots actually arrive at (512 features/rank)
    d2 = 512
    snaps2 = rng.randn(total, d2).astype(np.float32)
    _run_dmd_protocol(snaps2, None, eigs=False)          # warm
    _run_dmd_protocol(snaps2, batch, eigs=False)
    _run_dmd_protocol(snaps2, batch, eigs=False, donate=False)
    w512_seq, c512_seq = _run_dmd_protocol(snaps2, None, eigs=False)
    w512_bat, c512_bat = _run_dmd_protocol(snaps2, batch, eigs=False)
    w512_nod, _ = _run_dmd_protocol(snaps2, batch, eigs=False, donate=False)

    n_rec = 64
    recs = [StreamRecord("vel", 0, 1, s,
                         rng.randn(1024).astype(np.float32))
            for s in range(n_rec)]
    reps = 30
    t0 = time.time()
    for _ in range(reps):
        for r in recs:
            decode(encode(r, compress="int8+zstd"))
    us_single = (time.time() - t0) / reps * 1e6
    t0 = time.time()
    for _ in range(reps):
        decode_batch(encode_batch(recs, compress="int8+zstd"))
    us_batch = (time.time() - t0) / reps * 1e6
    bytes_single = sum(len(encode(r, compress="int8+zstd")) for r in recs)
    bytes_batch = len(encode_batch(recs, compress="int8+zstd"))

    result = {
        "config": {"d": d, "snapshots": total, "dmd_batch": batch,
                   "codec_records": n_rec, "backend": jax.default_backend()},
        "streaming_dmd": {
            "per_snapshot": {"wall_us": wall_seq * 1e6, "transfers": t_seq,
                             **c_seq},
            "batched": {"wall_us": wall_bat * 1e6, "transfers": t_bat,
                        **c_bat},
            "speedup": wall_seq / wall_bat,
            "transfer_ratio": t_seq / max(t_bat, 1),
            # eigen-solve cadence excluded: updates only
            "update_only": {
                "per_snapshot_us": wall_useq * 1e6,
                "batched_us": wall_ubat * 1e6,
                "speedup": wall_useq / wall_ubat,
                "device_calls": [c_useq["device_calls"],
                                 c_ubat["device_calls"]],
                "h2d": [c_useq["h2d"], c_ubat["h2d"]],
            },
            "update_only_d512": {
                "per_snapshot_us": w512_seq * 1e6,
                "batched_us": w512_bat * 1e6,
                "batched_no_donate_us": w512_nod * 1e6,
                "speedup": w512_seq / w512_bat,
                "device_calls": [c512_seq["device_calls"],
                                 c512_bat["device_calls"]],
                "h2d": [c512_seq["h2d"], c512_bat["h2d"]],
            },
        },
        "record_codec": {
            "single_x64_us": us_single,
            "batch_64_us": us_batch,
            "speedup": us_single / us_batch,
            "bytes_single_sum": bytes_single,
            "bytes_batch": bytes_batch,
        },
    }
    if write_json:
        BENCH_JSON.write_text(json.dumps(result, indent=2) + "\n")
    sd = result["streaming_dmd"]
    return [("hotpath_dmd_per_snapshot_64", sd["per_snapshot"]["wall_us"],
             f"{t_seq}xfers/{c_seq['device_calls']}calls"),
            ("hotpath_dmd_batched_64", sd["batched"]["wall_us"],
             f"{t_bat}xfers/{c_bat['device_calls']}calls "
             f"{sd['speedup']:.1f}x"),
            ("hotpath_dmd_update_only_64", sd["update_only"]["batched_us"],
             f"{sd['update_only']['speedup']:.1f}x vs per-snapshot"),
            ("hotpath_dmd_update_only_d512", sd["update_only_d512"]["batched_us"],
             f"{sd['update_only_d512']['speedup']:.1f}x vs per-snapshot"),
            ("hotpath_codec_single_x64", us_single, f"{bytes_single}B"),
            ("hotpath_codec_batch_64", us_batch,
             f"{bytes_batch}B {us_single/us_batch:.1f}x")]


def bench_multikey(write_json: bool = True):
    """Per-pane ``window_dmd`` loop vs one vmapped ``batched_window_dmd``
    dispatch across k co-fired keys (the BatchAggregate fast path).  Pane
    lengths are ragged on purpose — bucketed padding must still coalesce
    them into O(distinct buckets) device calls, not O(k)."""
    rng = np.random.RandomState(0)
    d, rank = 256, 8
    lens = (8, 10, 12, 16)        # pads to the {8, 16} column buckets
    result = {"config": {"d": d, "rank": rank, "pane_lens": list(lens),
                         "backend": jax.default_backend()}, "k": {}}
    rows = []
    for k in (4, 16, 32):
        panes = [[rng.randn(d).astype(np.float32)
                  for _ in range(lens[i % len(lens)])] for i in range(k)]
        for p in panes:                                  # warm per-bucket jit
            window_dmd(p, rank=rank, n_features=d)
        batched_window_dmd(panes, rank=rank, n_features=d)

        # best-of-N: scheduler noise only ever ADDS time, and it penalizes
        # the short batched dispatch disproportionately
        def _best(fn, trials=7):
            best = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best * 1e6

        us_loop = _best(lambda: [window_dmd(p, rank=rank, n_features=d)
                                 for p in panes])
        us_bat = _best(lambda: batched_window_dmd(panes, rank=rank,
                                                  n_features=d))
        result["k"][str(k)] = {"per_pane_us": us_loop, "batched_us": us_bat,
                               "speedup": us_loop / us_bat}
        rows.append((f"multikey_dmd_k{k}_d{d}", us_bat,
                     f"{us_loop / us_bat:.1f}x vs per-pane loop"))
    if write_json:
        MULTIKEY_JSON.write_text(json.dumps(result, indent=2) + "\n")
    return rows


def _gate_multikey(min_speedup: float = 3.0):
    """CI gate: the batched path must hold >= min_speedup at k >= 16."""
    data = json.loads(MULTIKEY_JSON.read_text())
    speedups = {int(k): v["speedup"] for k, v in data["k"].items()}
    bad = {k: round(s, 2) for k, s in speedups.items()
           if k >= 16 and s < min_speedup}
    if bad:
        raise SystemExit(
            f"multikey gate FAILED: batched speedup < {min_speedup}x at {bad}")
    print(f"# multikey gate OK: " + ", ".join(
        f"k={k}: {s:.1f}x" for k, s in sorted(speedups.items())))


SECTIONS = {"attention": bench_attention, "gram": bench_gram,
            "ssd": bench_ssd, "codec": bench_codec, "dmd": bench_dmd,
            "hotpath": bench_hotpath, "multikey": bench_multikey}


def main(csv=True, only: str | None = None, gate: bool = False):
    want = list(SECTIONS) if not only else only.split(",")
    unknown = [n for n in want if n not in SECTIONS]
    if unknown:
        raise SystemExit(f"unknown section(s) {unknown}; "
                         f"choose from: {','.join(SECTIONS)}")
    rows = []
    for name in want:
        rows.extend(SECTIONS[name]())
    if csv:
        print("kernel,us_per_call,derived")
        for name, us, d in rows:
            print(f"{name},{us:.1f},{d}")
    if gate and "multikey" in want:
        _gate_multikey()
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma list of: " + ",".join(SECTIONS))
    p.add_argument("--gate", action="store_true",
                   help="fail unless batched multikey DMD >= 3x at k >= 16")
    args = p.parse_args()
    main(only=args.only, gate=args.gate)
