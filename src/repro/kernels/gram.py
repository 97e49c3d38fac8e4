"""Pallas TPU Gram-accumulation kernels over snapshot blocks.

Two entry points serve the streaming-DMD hot loop (analysis/dmd.py):

* ``gram_accumulate(x, g)`` — G += XᵀX for a single snapshot block.
* ``gram_pair_accumulate(x, y, g, a)`` — the **fused** online-DMD update
  G += XᵀX, A += YᵀX in one ``pallas_call``.  X tiles are shared between
  both products (the (k, j) tile feeds the MXU twice), two f32 VMEM
  scratch accumulators run across the n-blocks, and the running G/A tiles
  are each read+written exactly once per call regardless of n.  This is
  what ``StreamingDMD.update_batch`` dispatches per micro-batch on TPU —
  one device call for the whole batch instead of two matmuls per snapshot.

Tiled (bd x bd) output blocks with the snapshot axis innermost in the grid.
MXU alignment: bd=128, bn=128 tiles (bf16/f32 both land on 128-lane vregs).
VMEM per step (fused): 3 input n-tiles + 4 d-tiles (g/a in+out) + 2 f32
scratch accumulators = 3*(bn*bd) + 6*(bd*bd) floats ≈ 576 KB at defaults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# full-f32 MXU products: the Gram route squares the snapshots' condition
# number, so a single bf16 pass would bury every direction below ~1e-2
# of the leading singular value (analysis/dmd.py)
_HIGHEST = jax.lax.Precision.HIGHEST


def _gram_kernel(xi_ref, xj_ref, g_ref, out_ref, acc_scr, *, n_n: int):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    xi = xi_ref[...].astype(F32)                       # (bn, bd)
    xj = xj_ref[...].astype(F32)                       # (bn, bd)
    acc_scr[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=F32)

    @pl.when(ni == n_n - 1)
    def _finish():
        out_ref[...] = (g_ref[...].astype(F32) + acc_scr[...]).astype(out_ref.dtype)


def gram_accumulate(x: jax.Array, g: jax.Array, *, block_d: int = 128,
                    block_n: int = 128, interpret: bool = False) -> jax.Array:
    """x: (n, d) snapshots; g: (d, d) running Gram.  Returns g + xᵀx."""
    n, d = x.shape
    block_d = min(block_d, d)
    block_n = min(block_n, n)
    nd = pl.cdiv(d, block_d)
    nn = pl.cdiv(n, block_n)
    dp, np_ = nd * block_d, nn * block_n
    if dp != d or np_ != n:
        x = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
        g = jnp.pad(g, ((0, dp - d), (0, dp - d)))

    kernel = functools.partial(_gram_kernel, n_n=nn)
    out = pl.pallas_call(
        kernel,
        grid=(nd, nd, nn),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp, dp), g.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, block_d), F32)],
        interpret=interpret,
    )(x, x, g)
    return out[:d, :d]


def _gram_pair_kernel(xi_ref, xj_ref, yi_ref, g_ref, a_ref, g_out, a_out,
                      g_acc, a_acc, *, n_n: int):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        a_acc[...] = jnp.zeros_like(a_acc)

    xi = xi_ref[...].astype(F32)                       # (bn, bd) X cols-i
    xj = xj_ref[...].astype(F32)                       # (bn, bd) X cols-j
    yi = yi_ref[...].astype(F32)                       # (bn, bd) Y cols-i
    dims = (((0,), (0,)), ((), ()))
    g_acc[...] += jax.lax.dot_general(xi, xj, dims, precision=_HIGHEST,
                                      preferred_element_type=F32)
    a_acc[...] += jax.lax.dot_general(yi, xj, dims, precision=_HIGHEST,
                                      preferred_element_type=F32)

    @pl.when(ni == n_n - 1)
    def _finish():
        g_out[...] = (g_ref[...].astype(F32) + g_acc[...]).astype(g_out.dtype)
        a_out[...] = (a_ref[...].astype(F32) + a_acc[...]).astype(a_out.dtype)


def gram_pair_accumulate(x: jax.Array, y: jax.Array, g: jax.Array,
                         a: jax.Array, *, block_d: int = 128,
                         block_n: int = 128, interpret: bool = False):
    """Fused online-DMD update: returns (g + xᵀx, a + yᵀx).

    x, y: (n, d) paired snapshot blocks (rows are (x_t, x_{t+1}) pairs);
    g, a: (d, d) running Gram / cross-Gram.  The X tiles are loaded once per
    grid step and feed both MXU products."""
    n, d = x.shape
    assert y.shape == x.shape, (x.shape, y.shape)
    block_d = min(block_d, d)
    block_n = min(block_n, n)
    nd = pl.cdiv(d, block_d)
    nn = pl.cdiv(n, block_n)
    dp, np_ = nd * block_d, nn * block_n
    if dp != d or np_ != n:
        x = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
        y = jnp.pad(y, ((0, np_ - n), (0, dp - d)))
        g = jnp.pad(g, ((0, dp - d), (0, dp - d)))
        a = jnp.pad(a, ((0, dp - d), (0, dp - d)))

    kernel = functools.partial(_gram_pair_kernel, n_n=nn)
    out_g, out_a = pl.pallas_call(
        kernel,
        grid=(nd, nd, nn),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((dp, dp), g.dtype),
                   jax.ShapeDtypeStruct((dp, dp), a.dtype)],
        scratch_shapes=[pltpu.VMEM((block_d, block_d), F32),
                        pltpu.VMEM((block_d, block_d), F32)],
        interpret=interpret,
    )(x, x, y, g, a)
    return out_g[:d, :d], out_a[:d, :d]
