"""Pallas TPU int8 blockwise quantize/dequantize.

One codec, three users: stream-record compression (core.records), cross-pod
gradient compression, and 8-bit optimizer moments (optim.adamw) — int8 data +
one f32 scale per row of Q elements.  Row-parallel grid; each kernel step
reduces |x| over its rows (VPU), scales, rounds, and writes int8 (the cast is
the memory win: 4x less HBM traffic on every moment read/write).

The scales travel through the kernel as an (nb, 1) column, not an (nb,)
vector: Mosaic tiles a 1-D block by its own length (256) while XLA tiles
the whole 1-D array (by up to 1024), so a 1-D scale block fails layout
verification on TPU as soon as the grid has more than one step.  A 2-D
(block_rows, 1) block uses the standard 2-D tiling on both sides.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(F32)                       # (bn, Q)
    # explicit multiply by 1/127: XLA rewrites division-by-constant into
    # multiply-by-reciprocal anyway, and the host codec (core.records)
    # must share the exact form for byte-identical wire frames
    scale = (jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-20)
             * (1.0 / 127.0))                        # (bn, 1)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(F32) * s_ref[...]


def quantize(x: jax.Array, *, block_rows: int = 256,
             interpret: bool = False):
    """x: (nb, Q) f32 -> (int8 (nb, Q), f32 scales (nb,))."""
    nb, Q = x.shape
    block_rows = min(block_rows, nb)
    g = pl.cdiv(nb, block_rows)
    nbp = g * block_rows
    if nbp != nb:
        x = jnp.pad(x, ((0, nbp - nb), (0, 0)))
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((block_rows, Q), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, Q), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, Q), jnp.int8),
            jax.ShapeDtypeStruct((nbp, 1), F32),
        ],
        interpret=interpret,
    )(x)
    return q[:nb], s[:nb, 0]


def dequantize(q: jax.Array, s: jax.Array, *, block_rows: int = 256,
               interpret: bool = False) -> jax.Array:
    nb, Q = q.shape
    block_rows = min(block_rows, nb)
    g = pl.cdiv(nb, block_rows)
    nbp = g * block_rows
    s = s.reshape(nb, 1)
    if nbp != nb:
        q = jnp.pad(q, ((0, nbp - nb), (0, 0)))
        s = jnp.pad(s, ((0, nbp - nb), (0, 0)))
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((block_rows, Q), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, Q), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, Q), F32),
        interpret=interpret,
    )(q, s)
    return x[:nb]
