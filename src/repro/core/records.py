"""Stream records: the Cloud-native unit ElasticBroker ships.

A record carries one field snapshot from one producer rank at one step,
exactly like the paper's ``broker_write(ctx, step, data, len)`` payloads:
timestep + serialized field data + schema, msgpack-framed, optionally
zstd-compressed or int8 block-quantized (the TPU-side Pallas ``quant`` kernel
implements the same codec in-graph; this is the host-side mirror).

Wire frames (first byte is the frame tag):

* ``M`` — one record, msgpack          * ``Z`` — one record, zstd(msgpack)
* ``B`` — record batch, msgpack        * ``C`` — record batch, zstd(msgpack)

Batched frames (``encode_batch``/``decode_batch``) amortize the per-message
cost that dominates streaming pipelines: N records share **one** msgpack
frame, **one** zstd pass, and **one** int8 quantization pass over the
concatenated payload buffer.  Identity columns (field/group/rank) collapse
to a scalar when uniform across the batch (the shared-schema header).
Optional delta encoding (``delta=True``) stores ``payload[i] -
payload[i-1]`` whenever record i-1 belongs to the same stream and has the
same shape — a big win for slowly-varying CFD fields under zstd/int8; the
``d`` flag column marks delta'd records and decode reconstructs the chain in
order (chains reset at every stream/shape change).  ``decode_any``
dispatches on the tag and always returns a list, so consumers
(Endpoint.push) are agnostic to framing.

int8 batch frames use **per-stream scales** (enc tag ``int8s``): quantization
blocks restart at every record boundary instead of running blindly over the
concatenated buffer, and deltas are **closed-loop** — each delta is taken
against the *dequantized* reconstruction of the previous record, so the
decoder's accumulated value is bitwise the encoder's reconstruction and
quantization error no longer accumulates along a delta chain (every record's
error is bounded by its own quantization step).  Legacy ``int8`` batch
frames (shared blocks over the concatenated buffer) still decode.

The uniform non-delta ``int8s`` rows path can quantize/dequantize through
the Pallas kernel (kernels/quant.py) instead of host numpy —
``set_quant_backend("auto"|"numpy"|"pallas")`` — with **byte-identical**
wire frames in both directions; numpy stays the reference oracle and the
CPU fallback.
"""
from __future__ import annotations

import struct
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import msgpack
import numpy as np
import zstandard as zstd

# zstd (de)compressor objects are not thread-safe, and every broker sender
# and endpoint is its own thread: one pair per thread, built on first use
_ZSTD = threading.local()


def _zstd_compress(blob: bytes) -> bytes:
    c = getattr(_ZSTD, "c", None)
    if c is None:
        c = _ZSTD.c = zstd.ZstdCompressor(level=1)
    return c.compress(blob)


def _zstd_decompress(blob: bytes) -> bytes:
    d = getattr(_ZSTD, "d", None)
    if d is None:
        d = _ZSTD.d = zstd.ZstdDecompressor()
    return d.decompress(blob)

QBLOCK = 256
# scale = max|block| * (1/127), as an explicit f32 multiply: XLA rewrites
# division-by-constant into multiply-by-reciprocal, so the kernel and the
# host path must share the multiply form for byte-identical frames
_INV127 = np.float32(1.0 / 127.0)


@dataclass(frozen=True)
class FieldSchema:
    """Registered at broker_init, mirrors the paper's field registration."""

    field_name: str              # e.g. "velocity_x", "resid_norm/layer"
    shape: tuple[int, ...]       # per-record payload shape
    dtype: str                   # numpy dtype name
    group_id: int                # producer group (paper: MPI process group)


@dataclass
class StreamRecord:
    field_name: str
    group_id: int
    rank: int                    # producer rank within the job
    step: int                    # simulation / training step
    payload: np.ndarray
    t_generated: float = field(default_factory=time.time)
    tenant: str = "default"      # QoS tenant class (repro.tenancy)

    def key(self) -> str:
        return f"{self.field_name}/g{self.group_id}/r{self.rank}"


# ---------------------------------------------------------------------------
# Wire codec
# ---------------------------------------------------------------------------

def quantize_int8(x: np.ndarray) -> dict:
    """Blockwise int8: flat blocks of QBLOCK with one f32 scale each — the
    host mirror of kernels/quant.py."""
    flat = np.asarray(x, np.float32).reshape(-1)
    pad = (-flat.size) % QBLOCK
    padded = np.pad(flat, (0, pad))
    blocks = padded.reshape(-1, QBLOCK)
    scale = np.maximum(np.abs(blocks).max(axis=1), 1e-20) * _INV127
    q = np.clip(np.round(blocks / scale[:, None]), -127, 127).astype(np.int8)
    return {"q": q.tobytes(), "scale": scale.astype(np.float32).tobytes(),
            "n": int(flat.size), "shape": list(x.shape)}


def dequantize_int8(d: dict) -> np.ndarray:
    q = np.frombuffer(d["q"], np.int8).reshape(-1, QBLOCK).astype(np.float32)
    scale = np.frombuffer(d["scale"], np.float32)
    flat = (q * scale[:, None]).reshape(-1)[: d["n"]]
    return flat.reshape(d["shape"])


def _quantize_stream(flat: np.ndarray) -> tuple[bytes, bytes]:
    """Record-local int8: blocks of QBLOCK restart at the record boundary and
    the last block is truncated (no padding on the wire).  Returns
    (q bytes — exactly flat.size — , per-block f32 scale bytes)."""
    n = flat.size
    nb = max(1, (n + QBLOCK - 1) // QBLOCK)
    padded = np.pad(flat, (0, nb * QBLOCK - n)).reshape(nb, QBLOCK)
    scale = np.maximum(np.abs(padded).max(axis=1), 1e-20) * _INV127
    q = np.clip(np.round(padded / scale[:, None]), -127, 127).astype(np.int8)
    return q.reshape(-1)[:n].tobytes(), scale.astype(np.float32).tobytes()


def _dequantize_stream(qb: bytes, sb: bytes, n: int,
                       q_off: int = 0, s_off: int = 0) -> np.ndarray:
    """Inverse of ``_quantize_stream`` reading at byte offsets into shared
    buffers (the batch frame concatenates every record's q/scale bytes)."""
    nb = max(1, (n + QBLOCK - 1) // QBLOCK)
    q = np.frombuffer(qb, np.int8, count=n, offset=q_off).astype(np.float32)
    scale = np.frombuffer(sb, np.float32, count=nb, offset=s_off)
    padded = np.pad(q, (0, nb * QBLOCK - n)).reshape(nb, QBLOCK)
    return (padded * scale[:, None]).reshape(-1)[:n]


def _quantize_stream_rows(mat: np.ndarray) -> tuple[bytes, bytes]:
    """Vectorized ``_quantize_stream`` over B same-length records (rows):
    one numpy pass instead of B, bitwise-identical bytes (blocks still
    restart at every record boundary).  This keeps the batched-frame
    encode cheaper than B single encodes on the broker hot path."""
    b, n = mat.shape
    nb = max(1, (n + QBLOCK - 1) // QBLOCK)
    padded = np.pad(mat, ((0, 0), (0, nb * QBLOCK - n))).reshape(b * nb,
                                                                 QBLOCK)
    scale = np.maximum(np.abs(padded).max(axis=1), 1e-20) * _INV127
    q = np.clip(np.round(padded / scale[:, None]), -127, 127).astype(np.int8)
    q = np.ascontiguousarray(q.reshape(b, nb * QBLOCK)[:, :n])
    return q.tobytes(), scale.astype(np.float32).tobytes()


def _dequantize_stream_rows(qb: bytes, sb: bytes, b: int, n: int) -> np.ndarray:
    """Vectorized ``_dequantize_stream`` for B same-length records; returns
    a (B, n) float32 array, bitwise-identical to the per-record path."""
    nb = max(1, (n + QBLOCK - 1) // QBLOCK)
    q = np.frombuffer(qb, np.int8, count=b * n).reshape(b, n).astype(
        np.float32)
    scale = np.frombuffer(sb, np.float32, count=b * nb)
    padded = np.pad(q, ((0, 0), (0, nb * QBLOCK - n))).reshape(b * nb, QBLOCK)
    return (padded * scale[:, None]).reshape(b, nb * QBLOCK)[:, :n]


# ---- device (Pallas) rows codec -------------------------------------------
# The uniform non-delta ``int8s`` path — the broker hot path — can run its
# quantization pass through kernels/quant.py instead of host numpy, so a
# device-resident producer never round-trips payloads through the host.
# Backend knob: "numpy" forces the host path, "pallas" forces the kernel
# (interpret mode off-TPU — what the parity tests pin), "auto" picks the
# kernel only on native accelerator backends.  The numpy path remains the
# reference oracle: both directions are **byte-identical** — same block
# layout, same scale formula (max|block|/127 with a 1e-20 floor), and both
# np.round and jnp.round round half to even.

_QUANT_BACKENDS = ("auto", "numpy", "pallas")
_quant_backend = "auto"


def set_quant_backend(mode: str) -> str:
    """Select the rows-codec backend; returns the previous setting."""
    global _quant_backend
    if mode not in _QUANT_BACKENDS:
        raise ValueError(f"quant backend must be one of {_QUANT_BACKENDS}")
    prev, _quant_backend = _quant_backend, mode
    return prev


def get_quant_backend() -> str:
    return _quant_backend


def _pallas_rows_active() -> bool:
    if _quant_backend == "numpy":
        return False
    if _quant_backend == "pallas":
        return True
    import jax     # lazy: records must import without touching jax
    return jax.default_backend() in ("tpu", "gpu")


def _quantize_stream_rows_pallas(mat: np.ndarray) -> tuple[bytes, bytes]:
    """``_quantize_stream_rows`` through the Pallas quant kernel.  Same
    (b·nb, QBLOCK) row layout, byte-identical output."""
    import jax.numpy as jnp
    from repro.kernels import ops
    b, n = mat.shape
    nb = max(1, (n + QBLOCK - 1) // QBLOCK)
    padded = np.pad(mat, ((0, 0), (0, nb * QBLOCK - n))).reshape(b * nb,
                                                                 QBLOCK)
    q, scale = ops.quantize(jnp.asarray(padded), block_rows=QBLOCK)
    q = np.asarray(q).reshape(b, nb * QBLOCK)[:, :n]
    return (np.ascontiguousarray(q).tobytes(),
            np.asarray(scale).astype(np.float32, copy=False).tobytes())


def _dequantize_stream_rows_pallas(qb: bytes, sb: bytes, b: int,
                                   n: int) -> np.ndarray:
    """``_dequantize_stream_rows`` through the Pallas dequant kernel."""
    import jax.numpy as jnp
    from repro.kernels import ops
    nb = max(1, (n + QBLOCK - 1) // QBLOCK)
    q = np.zeros((b, nb * QBLOCK), np.int8)
    q[:, :n] = np.frombuffer(qb, np.int8, count=b * n).reshape(b, n)
    scale = np.frombuffer(sb, np.float32, count=b * nb)
    x = ops.dequantize(jnp.asarray(q.reshape(b * nb, QBLOCK)),
                       jnp.asarray(scale), block_rows=QBLOCK)
    return np.asarray(x).reshape(b, nb * QBLOCK)[:, :n]


def _quant_rows(mat: np.ndarray) -> tuple[bytes, bytes]:
    if _pallas_rows_active():
        return _quantize_stream_rows_pallas(mat)
    return _quantize_stream_rows(mat)


def _dequant_rows(qb: bytes, sb: bytes, b: int, n: int) -> np.ndarray:
    if _pallas_rows_active():
        return _dequantize_stream_rows_pallas(qb, sb, b, n)
    return _dequantize_stream_rows(qb, sb, b, n)


def encode(rec: StreamRecord, *, compress: str = "zstd") -> bytes:
    """compress: none | zstd | int8 | int8+zstd."""
    arr = np.asarray(rec.payload)
    if compress.startswith("int8"):
        payload: Any = quantize_int8(arr)
        enc = "int8"
    else:
        payload = {"raw": arr.astype(np.float32).tobytes(),
                   "shape": list(arr.shape)}
        enc = "raw"
    msg = {
        "f": rec.field_name, "g": rec.group_id, "r": rec.rank,
        "s": rec.step, "t": rec.t_generated, "e": enc, "p": payload,
    }
    if rec.tenant != "default":
        # the tenant column only appears on tagged traffic, so default-tenant
        # frames stay byte-identical with pre-tenancy peers
        msg["u"] = rec.tenant
    blob = msgpack.packb(msg, use_bin_type=True)
    if compress.endswith("zstd"):
        return b"Z" + _zstd_compress(blob)
    return b"M" + blob


def decode(data: bytes) -> StreamRecord:
    tag, blob = data[:1], data[1:]
    if tag == b"Z":
        blob = _zstd_decompress(blob)
    msg = msgpack.unpackb(blob, raw=False)
    if msg["e"] == "int8":
        payload = dequantize_int8(msg["p"])
    else:
        payload = np.frombuffer(msg["p"]["raw"], np.float32).reshape(
            msg["p"]["shape"])
    return StreamRecord(field_name=msg["f"], group_id=msg["g"], rank=msg["r"],
                        step=msg["s"], payload=payload, t_generated=msg["t"],
                        tenant=msg.get("u", "default"))


# ---------------------------------------------------------------------------
# Batched wire codec — one frame / one zstd pass / one quant pass per N recs
# ---------------------------------------------------------------------------

def _pack_col(vals: list):
    """Shared-schema header: collapse a uniform identity column to a scalar."""
    return vals[0] if all(v == vals[0] for v in vals) else list(vals)


def _unpack_col(v, n: int) -> list:
    return list(v) if isinstance(v, list) else [v] * n


def encode_batch(recs: list[StreamRecord], *, compress: str = "zstd",
                 delta: bool = False) -> bytes:
    """Encode N records into one aggregated wire frame.

    compress: none | zstd | int8 | int8+zstd (same modes as ``encode``).
    delta: store payload[i] - payload[i-1] when record i-1 is from the same
    stream with the same shape (flagged per record in the ``d`` column).
    With int8, deltas are closed-loop (taken against the dequantized
    reconstruction) so chain error never accumulates; with raw floats,
    reconstruction is float-exact only to roundoff ((b-a)+a can differ from
    b in the last ulp) — disable delta where bitwise fidelity matters.
    """
    if not recs:
        raise ValueError("encode_batch needs at least one record")
    flags: list[int] = []
    if compress.startswith("int8"):
        # per-stream scales + closed-loop deltas (enc tag "int8s")
        flats = [np.asarray(r.payload, np.float32).reshape(-1) for r in recs]
        sizes = {f.size for f in flats}
        if not delta and len(sizes) == 1:
            # uniform non-delta batch (the broker hot path): one vectorized
            # quantization pass over all records at once
            qb, sb = _quant_rows(np.stack(flats))
            flags = [0] * len(recs)
            payload: Any = {"q": qb, "scale": sb}
        else:
            qs, scales = [], []
            prev_key = prev_shape = None
            prev_recon = None
            for rec, flat in zip(recs, flats):
                shape = np.asarray(rec.payload).shape
                chained = (delta and prev_recon is not None
                           and rec.key() == prev_key and shape == prev_shape)
                src = flat - prev_recon if chained else flat
                flags.append(1 if chained else 0)
                qb, sb = _quantize_stream(src)
                qs.append(qb)
                scales.append(sb)
                recon = _dequantize_stream(qb, sb, flat.size)
                if chained:
                    recon = recon + prev_recon
                prev_key, prev_shape, prev_recon = rec.key(), shape, recon
            payload = {"q": b"".join(qs), "scale": b"".join(scales)}
        enc = "int8s"
    else:
        flats = []
        prev_key = prev_shape = None
        prev_flat = None
        for rec in recs:
            arr = np.asarray(rec.payload, np.float32)
            flat = arr.reshape(-1)
            if (delta and prev_flat is not None and rec.key() == prev_key
                    and arr.shape == prev_shape):
                flats.append(flat - prev_flat)
                flags.append(1)
            else:
                flats.append(flat)
                flags.append(0)
            prev_key, prev_shape, prev_flat = rec.key(), arr.shape, flat
        buf = np.concatenate(flats) if flats else np.zeros(0, np.float32)
        payload = {"raw": buf.tobytes()}
        enc = "raw"
    msg = {
        "n": len(recs),
        "f": _pack_col([r.field_name for r in recs]),
        "g": _pack_col([r.group_id for r in recs]),
        "r": _pack_col([r.rank for r in recs]),
        "s": [r.step for r in recs],
        "t": [r.t_generated for r in recs],
        "e": enc,
        "d": flags if any(flags) else 0,
        "sh": [list(np.asarray(r.payload).shape) for r in recs],
        "p": payload,
    }
    if any(r.tenant != "default" for r in recs):
        # uniform-collapsed like the other identity columns; absent entirely
        # for default-only batches (frame bytes unchanged vs. pre-tenancy)
        msg["u"] = _pack_col([r.tenant for r in recs])
    blob = msgpack.packb(msg, use_bin_type=True)
    if compress.endswith("zstd"):
        return b"C" + _zstd_compress(blob)
    return b"B" + blob


def decode_batch(data: bytes) -> list[StreamRecord]:
    tag, blob = data[:1], data[1:]
    if tag == b"C":
        blob = _zstd_decompress(blob)
    msg = msgpack.unpackb(blob, raw=False)
    n = msg["n"]
    per_stream = msg["e"] == "int8s"
    if msg["e"] == "int8":          # legacy frames: shared concatenated blocks
        d = dict(msg["p"])
        d["shape"] = [d["n"]]   # flatten; per-record shapes applied below
        buf = dequantize_int8(d)
    elif not per_stream:
        buf = np.frombuffer(msg["p"]["raw"], np.float32)
    fields = _unpack_col(msg["f"], n)
    groups = _unpack_col(msg["g"], n)
    ranks = _unpack_col(msg["r"], n)
    tenants = _unpack_col(msg.get("u", "default"), n)
    flags = _unpack_col(msg["d"], n) if msg["d"] else [0] * n
    shapes = [tuple(s) for s in msg["sh"]]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    rows = None
    if per_stream and not any(flags) and len(set(sizes)) == 1:
        rows = _dequant_rows(msg["p"]["q"], msg["p"]["scale"], n, sizes[0])
    out: list[StreamRecord] = []
    off = q_off = s_off = 0
    prev_flat = None
    for i in range(n):
        shape, size = shapes[i], sizes[i]
        if rows is not None:
            flat = rows[i]
        elif per_stream:
            flat = _dequantize_stream(msg["p"]["q"], msg["p"]["scale"], size,
                                      q_off=q_off, s_off=s_off)
            q_off += size
            s_off += 4 * max(1, (size + QBLOCK - 1) // QBLOCK)
        else:
            flat = buf[off: off + size]
            off += size
        if flags[i]:
            flat = flat + prev_flat
        prev_flat = flat
        out.append(StreamRecord(field_name=fields[i], group_id=groups[i],
                                rank=ranks[i], step=msg["s"][i],
                                payload=flat.reshape(shape),
                                t_generated=msg["t"][i],
                                tenant=tenants[i]))
    return out


def decode_any(data: bytes) -> list[StreamRecord]:
    """Tag-dispatching decode: single-record or batch frame -> list."""
    if data[:1] == b"S":                    # seq-wrapped exactly-once frame
        data = unwrap_seq(data)[2]
    if data[:1] in (b"B", b"C"):
        return decode_batch(data)
    return [decode(data)]


# ---- exactly-once delivery framing (tag ``S``) -----------------------------
# ``S`` + base_seq(u64) + count(u32) + inner frame.  The WAL sequence range
# [base, base+count) travels in-band with the frame so the Transport protocol
# is untouched; endpoints unwrap it for receive-side dedupe (runtime.wal).
_SEQ_HDR = struct.Struct("!QI")


def wrap_seq(base_seq: int, count: int, blob: bytes) -> bytes:
    """Prefix a wire frame with its WAL seq range (exactly-once delivery)."""
    return b"S" + _SEQ_HDR.pack(base_seq, count) + blob


def unwrap_seq(data: bytes) -> tuple[int | None, int, bytes]:
    """Split a seq-wrapped frame into (base_seq, count, inner).  Frames
    without the ``S`` tag pass through as (None, 0, data)."""
    if data[:1] != b"S":
        return None, 0, data
    base, count = _SEQ_HDR.unpack_from(data, 1)
    return base, count, data[1 + _SEQ_HDR.size:]
