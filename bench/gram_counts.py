"""Operations and bytes of the Pallas ``gram_pair`` kernel, from its shapes.

``kernels/gram.py`` ``gram_pair_accumulate`` folds an (n, d) pair block
into the running (d, d) Gram and cross-Gram: G += XᵀX, A += YᵀX.  What the
algorithm must do: 2·n·d² multiply-adds per product, so 4·n·d² operations;
X and Y read once, G and A each read and written once.
"""
from __future__ import annotations

from bench import counts

# the op's name in the trace: the jitted wrapper in kernels/ops.py
KERNEL = "_gram_pair_raw"


def gram_pair(n: int, d: int) -> tuple[int, int]:
    """(ops, bytes) of one update with an (n, d) float32 pair block."""
    return 4 * n * d * d, (2 * n * d + 4 * d * d) * counts.F32


def call_counts(arrays) -> tuple[int, int] | None:
    """(ops, HBM bytes) of one kernel call from its op's arrays
    (``bench.trace.arrays``, results first): G' and A', then X, X, Y, G, A.
    X is handed to the kernel twice, once for each of its tile streams, and
    counted once; an array in VMEM is not counted (``counts.hbm_bytes``).
    None where the arrays are not those of a pair update."""
    if len(arrays) != 7 or any(dt != "f32" for dt, _dims, _sp in arrays):
        return None
    results, operands = arrays[:2], arrays[2:]
    (n, d) = operands[0][1]
    if operands[1][1] != (n, d) or operands[2][1] != (n, d) \
            or any(a[1] != (d, d) for a in results + operands[3:]):
        return None
    return 4 * n * d * d, counts.hbm_bytes(results + operands[:1] + operands[2:])
