"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def attention_ref(q, k, v, *, causal: bool = True, window: int | None = None,
                  scale: float | None = None):
    """Naive full-matrix attention.  q: (B,S,H,D); k/v: (B,T,H,D) (pre-expanded
    KV heads).  Returns (B,S,H,D) in q.dtype."""
    B, S, H, D = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    s = jnp.einsum("bshd,bthd->bhst", q.astype(F32), k.astype(F32)) * scale
    qpos = jnp.arange(S)[:, None]
    tpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= tpos <= qpos
    if window is not None:
        mask &= tpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", p, v.astype(F32))
    return o.astype(q.dtype)


def gram_ref(x: jax.Array, g: jax.Array | None = None) -> jax.Array:
    """G += XᵀX.  x: (n, d) snapshot block; g: (d, d) running Gram or None."""
    upd = jnp.dot(x.T.astype(F32), x.astype(F32))
    return upd if g is None else g.astype(F32) + upd


def gram_pair_ref(x: jax.Array, y: jax.Array, g: jax.Array | None = None,
                  a: jax.Array | None = None):
    """Fused online-DMD update: (G += XᵀX, A += YᵀX).  x, y: (n, d) paired
    snapshot blocks; g, a: (d, d) running Gram / cross-Gram or None."""
    xf, yf = x.astype(F32), y.astype(F32)
    gu = jnp.dot(xf.T, xf)
    au = jnp.dot(yf.T, xf)
    return (gu if g is None else g.astype(F32) + gu,
            au if a is None else a.astype(F32) + au)


def ssd_intra_ref(cb, cum, bmat, xdt):
    """Oracle for kernels/ssd.py — the formulas from models/mamba.py.

    cb: (G,L,L); cum: (G,L,H); bmat: (G,L,N); xdt: (G,L,H,P)."""
    decay = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])   # (G,i,j,H)
    L = cb.shape[1]
    mask = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    m = cb[..., None] * decay * mask[None, :, :, None]
    y = jnp.einsum("gijh,gjhp->gihp", m, xdt)
    seg = jnp.exp(cum[:, -1:, :] - cum)                        # (G,L,H)
    s = jnp.einsum("gjn,gjh,gjhp->ghnp", bmat, seg, xdt)
    return y, s


def quant_ref(x: jax.Array):
    """Blockwise int8 over rows.  x: (nb, q) f32 -> (int8 (nb,q), f32 (nb,))."""
    scale = (jnp.maximum(jnp.max(jnp.abs(x.astype(F32)), axis=1), 1e-20)
             * (1.0 / 127.0))
    data = jnp.clip(jnp.round(x.astype(F32) / scale[:, None]), -127, 127)
    return data.astype(jnp.int8), scale


def dequant_ref(data: jax.Array, scale: jax.Array) -> jax.Array:
    return data.astype(F32) * scale[:, None]


def window_eigs_ref(snaps: jax.Array, n_valid: int, rank: int) -> jax.Array:
    """Oracle for ``analysis.dmd._masked_window_operator`` + ``_small_eigs``:
    SVD-route exact DMD on the *valid slice* of a zero-padded (d, m) pane
    of snapshot columns (the transpose of the (m, d) rows the operator
    takes), eigenvalues sorted by descending magnitude.  Host-side only
    (``n_valid`` must be concrete; the masked solve exists precisely to
    avoid this dynamic slice).  CPU-only oracle: ``jnp.linalg.eigvals`` has
    no TPU lowering, which is why the solve under test takes its small
    eigensolve on the host."""
    X = snaps[:, : n_valid - 1].astype(F32)
    Y = snaps[:, 1:n_valid].astype(F32)
    U, S, Vt = jnp.linalg.svd(X, full_matrices=False)
    r = min(rank, S.shape[0])
    U, S, Vt = U[:, :r], S[:r], Vt[:r]
    good = S > 1e-7 * jnp.maximum(S[0], 1e-30)
    Sinv = jnp.where(good, 1.0 / jnp.maximum(S, 1e-30), 0.0)
    Atilde = (U.T @ Y @ Vt.T * Sinv[None, :]) * good[:, None] * good[None, :]
    eigs = jnp.linalg.eigvals(Atilde)
    return eigs[jnp.argsort(-jnp.abs(eigs))]
