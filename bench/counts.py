"""Operations and bytes each kernel needs, from its shapes alone.

A kernel's roofline share is the least time the chip could take for the
work — the larger of operations over peak rate and bytes over peak HBM
bandwidth (``peaks.json``) — divided by the time the trace gives the
kernel.  The counts are what the algorithm must do, not what a particular
tiling happens to do: inputs read once, outputs written once.
"""
from __future__ import annotations

import json
from pathlib import Path

F32, I8 = 4, 1
ITEMSIZE = {"f32": F32, "s8": I8, "bf16": 2, "s32": 4}
QBLOCK = 256                    # floats per int8 block (one kernel row)

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def quant(rows: int, cols: int = QBLOCK) -> tuple[int, int]:
    """(ops, bytes) of ``quantize`` on a (rows, cols) float32 block: read
    the floats; |x|, row max, divide, round per element; write int8 codes
    and one float32 scale per row."""
    n = rows * cols
    return 4 * n, n * F32 + n * I8 + rows * F32


def dequant(rows: int, cols: int = QBLOCK) -> tuple[int, int]:
    """(ops, bytes) of ``dequantize``: read codes and scales, one multiply
    per element, write float32."""
    n = rows * cols
    return n, n * I8 + rows * F32 + n * F32


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``, exactly as JAX reports the kind.
    An unknown kind is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float | None:
    """Least possible time over measured time, in %, or None without a
    measured time.  Operations are held to the bf16 peak, the chip's
    fastest, so the share is never flattered."""
    if seconds <= 0:
        return None
    pk = peaks(device_kind)
    least = max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def codec_kernel(op_name: str) -> str | None:
    """Which codec kernel a Pallas call is, by its op's name in the trace
    (the jitted wrappers ``_quantize`` / ``_dequantize`` of kernels/ops.py)."""
    return {"_quantize": "quant", "_dequantize": "dequant"}.get(op_name)


def codec_rows(kind: str, arrays) -> tuple[str, tuple[int, ...]] | None:
    """(dtype, dims) of the (rows, QBLOCK) operand a codec call reads, from
    its op's arrays (``bench.trace.arrays``, results first): float32 for
    ``quant``, int8 for ``dequant``."""
    want = "f32" if kind == "quant" else "s8"
    found = None
    for dtype, dims, _space in arrays:
        if dtype == want and len(dims) == 2 and dims[1] == QBLOCK:
            found = dtype, dims         # the last one: operands follow results
    return found


def hbm_bytes(arrays) -> int:
    """Bytes of the arrays (``bench.trace.arrays``) that live in HBM
    (memory space 0).  An array in VMEM is moved by a copy of its own,
    outside the kernel, and the chip's VMEM bandwidth is not published, so
    it sets no bound on the kernel's time."""
    total = 0
    for dtype, dims, space in arrays:
        if space == 0:
            n = ITEMSIZE[dtype]
            for x in dims:
                n *= x
            total += n
    return total
