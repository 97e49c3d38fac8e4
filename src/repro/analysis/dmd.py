"""Dynamic Mode Decomposition in JAX — the paper's Cloud-side analysis.

Three entry points, one eigensolve:

* ``exact_dmd`` — PyDMD-equivalent batch DMD on a snapshot window
  (thin SVD -> rank-r reduced operator -> eigenvalues).
* ``window_dmd`` / ``batched_window_dmd`` — the stream-operator entry
  points.  Both route through the *method-of-snapshots* reduction
  ``_masked_window_operator``: the reduced operator comes from the (m, m)
  snapshot Gram matrix instead of the (d, m) SVD, so a window of d=512
  features costs one ``(m, d)·(m, d)`` einsum plus a small ``eigh``.
  Because validity is a mask rather than a shape, panes are zero-padded to
  power-of-two buckets (snapshots, features where the caller does not fix
  them, and — for the batched entry — pane count), the jit cache stays
  O(log) across ragged windows, and ``batched_window_dmd`` vmaps the whole
  reduction across co-fired panes in a single device dispatch.
* ``StreamingDMD`` — online DMD over unbounded streams: Gram updates
  G += XᵀX, A += YᵀX over snapshot-pair blocks, eigenvalues from the
  Gram-space operator (``_gram_operator``): the full ``eigh`` of G on a
  stream's first Gram solve (``gram_eigs``), then block subspace iteration
  warm-started from the previous solve's basis.  This is what each
  stream's executor runs per micro-batch.

Every path splits the same way.  The device does the heavy work — Gram
products, the thin SVD or ``eigh``, and the projection down to a rank-r
reduced operator (r <= ``rank``, a ``(k, r, r)`` stack for batched panes)
— and ``_small_eigs`` takes the eigenvalues of those r×r operators on the
host with ``np.linalg.eigvals``: a nonsymmetric eigensolve has no TPU
lowering, and 64 floats per pane is all that crosses back.  The Gram
route stops one step earlier: the device projects G and A onto G's top-p
Ritz basis, and the host finishes the p×p eigh and the projection to r×r
in float64 (``_ritz_eigs``).  The same path
runs on every backend, so CPU tests cover the code the chip runs.  The
device matmuls run at full f32 precision (``_PRECISION``): TPU's default
single bf16 pass would bury every direction below ~1e-2 of the leading
singular value, and the Gram route squares that ratio.

``StreamingDMD`` is **device-resident**: G and A live as ``jax.Array`` and
never round-trip through the host between updates.  The batched entry point
``update_batch((n, d) snapshots)`` forms the shifted X/Y pair in one shot
and issues a single device call per micro-batch — the fused Pallas
``gram_pair`` kernel (kernels/gram.py) on TPU, a jitted jnp matmul pair
elsewhere — instead of one ``G += x xᵀ, A += y xᵀ`` dispatch (plus four
host↔device transfers) per snapshot.  The update **donates** G/A into the
jitted accumulator (``donate_argnums``) so XLA updates them in place
instead of allocating a fresh (d, d) pair per micro-batch, and
``eigenvalues()`` caches its last solve until the next update lands.  The
Gram solve's top-p Ritz basis stays on the device too, as the next solve's
warm start.
``h2d_transfers`` / ``d2h_transfers`` / ``device_calls`` counters make the
savings measurable (benchmarks/kernels_bench.py writes them to
BENCH_hotpath.json / BENCH_multikey.json).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.telemetry import span

F32 = jnp.float32
_PRECISION = "highest"       # full-f32 device matmuls (see module docstring)
# Every route keeps a direction only if its squared singular value s² (a
# Gram eigenvalue) exceeds this fraction of the largest.  In f32 a Gram
# eigenvalue carries an absolute error of a few eps·s0² (more after long
# accumulations), so a direction near 1e-5 is known only to tens of
# percent — and a badly resolved direction in the reduced operator
# perturbs every eigenvalue, the leading one included.  Above 1e-4 the
# CFD slab spectra (fast-decaying) stay within ~1e-3 of a float64 SVD
# reference; at 1e-5 they were off by up to 2.5e-2.  Directions below it
# are also under the int8 wire codec's noise floor (~2e-3 of a block's
# largest value).
_REL_TOL = 1e-4


def _small_eigs(M, n_good) -> np.ndarray:
    """The one DMD eigensolve: eigenvalues of rank-r reduced operators.

    ``M``: (..., r, r) operators from a device reduction; ``n_good``: (...)
    count of trustworthy directions in each.  Bad directions arrive as zero
    columns of M (block-triangular), so they contribute exact-zero
    eigenvalues; after the magnitude-descending sort they sit last and are
    masked to NaN, which consumers filter.  Runs on the host with
    ``np.linalg.eigvals`` — JAX has no TPU lowering for a nonsymmetric
    eigensolve — and returns complex64.  An operator with a non-finite
    entry yields all-NaN eigenvalues instead of raising."""
    M = np.asarray(M, np.float64)
    n_good = np.asarray(n_good)
    finite = np.isfinite(M).all(axis=(-2, -1))
    eigs = np.linalg.eigvals(np.where(finite[..., None, None], M, 0.0))
    order = np.argsort(-np.abs(eigs), axis=-1, kind="stable")
    eigs = np.take_along_axis(eigs, order, axis=-1)
    keep = (np.arange(M.shape[-1]) < n_good[..., None]) & finite[..., None]
    return np.where(keep, eigs, np.nan).astype(np.complex64)


@partial(jax.jit, static_argnames=("rank",))
def _exact_operator(snapshots: jax.Array, rank: int):
    """Device half of ``exact_dmd``: (A~ (r, r), #good directions, energy)."""
    with jax.default_matmul_precision(_PRECISION):
        X = snapshots[:, :-1].astype(F32)
        Y = snapshots[:, 1:].astype(F32)
        U, S, Vt = jnp.linalg.svd(X, full_matrices=False)
        r = min(rank, S.shape[0])
        good = S[:r] ** 2 > _REL_TOL * jnp.maximum(S[0] ** 2, 1e-30)
        Sinv = jnp.where(good, 1.0 / S[:r], 0.0)
        Atilde = U[:, :r].T @ Y @ Vt[:r].T * Sinv[None, :]
        energy = jnp.sum(S[:r] ** 2) / jnp.maximum(jnp.sum(S ** 2), 1e-30)
    return Atilde, jnp.sum(good), energy


def exact_dmd(snapshots, rank: int = 8) -> tuple[np.ndarray, float]:
    """snapshots: (n_features, n_steps).  Returns (eigenvalues, energy).

    X = snaps[:, :-1], Y = snaps[:, 1:];  A~ = Uᵀ Y V S⁻¹ (rank-truncated).
    Eigenvalues come magnitude-descending; directions below ``_REL_TOL``
    are NaN.
    """
    Atilde, n_good, energy = _exact_operator(jnp.asarray(snapshots),
                                             rank=rank)
    return _small_eigs(Atilde, n_good), float(energy)


@jax.jit
def gram_update(G: jax.Array, A: jax.Array, x: jax.Array, y: jax.Array):
    """Rank-1 online-DMD update: G += x xᵀ, A += y xᵀ (single-pair oracle)."""
    return G + jnp.outer(x, x), A + jnp.outer(y, x)


def _gram_pair_raw(G: jax.Array, A: jax.Array, X: jax.Array, Y: jax.Array):
    """Batched online-DMD update: G += XᵀX, A += YᵀX over (n, d) pair blocks.

    The portable jnp form of the fused Pallas ``gram_pair`` kernel
    (kernels/gram.py) and its allclose oracle.  All-zero padding rows are
    no-ops in both products, so callers may pad n freely."""
    Xf, Yf = X.astype(F32), Y.astype(F32)
    with jax.default_matmul_precision(_PRECISION):
        return G + Xf.T @ Xf, A + Yf.T @ Xf


gram_pair_update = jax.jit(_gram_pair_raw)
# donated flavor: XLA reuses the incoming G/A buffers for the outputs —
# the hot loop stops allocating a fresh (d, d) pair per micro-batch.
# Callers must not read the donated arrays afterwards (StreamingDMD
# rebinds self._G/_A to the results, so nothing ever does).
gram_pair_update_donated = jax.jit(_gram_pair_raw, donate_argnums=(0, 1))


# Warm start of the Gram eigensolve.  Between two solves of a stream G
# gains one small-rank term, so the previous solve's top-p Ritz basis lies
# close to the new one, and block subspace iteration from it reaches the
# Ritz pairs the full eigh would give.  Each iteration shrinks the error of
# kept direction i by s_{p+1}/s_i: 1e-5 to 2e-3 on the CFD slab streams,
# where the residuals ‖G uᵢ − sᵢ uᵢ‖ reach their f32 floor (1e-7 to 1e-6
# of s0) within an iteration and stop falling there.  A residual test
# alone therefore cannot tell one iteration from three, and a spectrum
# that decays more slowly needs more: at least _WARM_MIN_ITERS, which
# shrink an error by (s_{p+1}/s_i)³, then the residual test at ~10× the
# floor.  A stream that does not meet it within _WARM_MAX_ITERS falls back
# to the full eigh.
_WARM_MIN_ITERS = 3
_WARM_TOL = 1e-5             # max ‖G uᵢ − sᵢ uᵢ‖ over kept i, ÷ s0
_WARM_MAX_ITERS = 30


def _rayleigh_ritz(G: jax.Array, Z: jax.Array):
    """G's Ritz pairs on span(Z), largest first: (s (p,), U (d, p), G U)."""
    Q, _ = jnp.linalg.qr(Z)
    GQ = G @ Q
    s, W = jnp.linalg.eigh(Q.T @ GQ)         # ascending
    W = W[:, ::-1]
    return s[::-1], Q @ W, GQ @ W


def _deflated_projection(G: jax.Array, A: jax.Array, s: jax.Array,
                         U: jax.Array):
    """(UᵀGU, UᵀAU), (p, p), for G's Ritz pairs (s, U).

    G's entries are of the order of s0, so an f32 product G U carries
    errors of ~ε·s0 in every column, and the kept directions' Ritz values
    reach down to ``_REL_TOL``·s0: that error, not the eigensolve, sets how
    far a direction near the cutoff moves.  Taking U diag(s) Uᵀ out of G
    entry by entry first leaves D = G − U diag(s) Uᵀ, whose entries are of
    the order of the residuals, so UᵀGU = diag(s) + UᵀDU and
    UᵀAU = UᵀGU + Uᵀ(A − G)U come out with errors relative to what they
    hold (A − G = (Y − X)ᵀX is small where the flow moves little between
    snapshots)."""
    D = G - (U * s[None, :]) @ U.T
    B = jnp.diag(s) + U.T @ (D @ U)
    return B, B + U.T @ ((A - G) @ U)


@partial(jax.jit, static_argnames=("rank",))
def _gram_operator(G: jax.Array, A: jax.Array, basis: jax.Array,
                   warm: jax.Array, rank: int):
    """Device half of the Gram route: (UᵀGU (p, p), UᵀAU (p, p), G's
    top-p Ritz basis U (d, p), warm iterations run, whether they fell
    back); ``_ritz_eigs`` is the host half.

    With ``warm`` true, block subspace iteration from ``basis`` (the
    previous solve's Ritz basis), each step one product with G and a
    Rayleigh-Ritz step, until the kept directions meet the residual test;
    otherwise, or if they do not within ``_WARM_MAX_ITERS``, the full
    ``eigh`` (TPU runs only the branch ``lax.cond`` takes).  ``basis`` and
    ``warm`` have one shape on every call, so one program serves both."""
    d = G.shape[0]
    p, r = min(2 * rank, d), min(rank, d)
    with jax.default_matmul_precision(_PRECISION):
        def more(c):
            k, conv = c[0], c[4]
            return (warm & (k < _WARM_MAX_ITERS)
                    & ((k < _WARM_MIN_ITERS) | ~conv))

        def iterate(c):
            k, _s, _U, GU, _conv = c
            s, U, GU = _rayleigh_ritz(G, GU)
            res = jnp.linalg.norm(GU[:, :r] - U[:, :r] * s[None, :r], axis=0)
            return (k + 1, s, U, GU,
                    jnp.max(res) <= _WARM_TOL * jnp.maximum(s[0], 1e-30))

        k, s, U, _GU, conv = jax.lax.while_loop(
            more, iterate,
            (jnp.int32(0), jnp.zeros(p, F32), basis, G @ basis,
             jnp.bool_(False)))

        def cold():
            # the full eigh, then one subspace-iteration + Rayleigh-Ritz
            # step on its top p directions: TPU's eigh leaves eigenvalue
            # errors of ~1e-5·‖G‖, which a direction at _REL_TOL cannot
            # afford; the small eigh of QᵀGQ resolves it to the f32
            # rounding of G itself
            _, V = jnp.linalg.eigh(G)            # ascending
            s, U, _ = _rayleigh_ritz(G, G @ V[:, -p:])
            return s, U

        s, U = jax.lax.cond(conv, lambda: (s, U), cold)
        B, C = _deflated_projection(G, A, s, U)
    return B, C, U, k, warm & ~conv


def _ritz_eigs(B, C, rank: int, rel_tol: float = _REL_TOL) -> np.ndarray:
    """Host half of the Gram route, in float64: the eigh of the (p, p)
    B = UᵀGU, its top ``rank`` directions W_r with s above ``rel_tol``·s0,
    M_r = W_rᵀ C W_r diag(1/s_r) for C = UᵀAU, and ``_small_eigs`` of it.
    A p×p eigh on the host costs microseconds and separates the kept
    directions from the dropped ones in float64, where an f32 eigh of B,
    whose entries span s0 down to s_p, would leave errors of ~ε·s0."""
    B, C = np.asarray(B, np.float64), np.asarray(C, np.float64)
    r = min(rank, B.shape[0])
    if not (np.isfinite(B).all() and np.isfinite(C).all()):
        return _small_eigs(np.full((r, r), np.nan), 0)
    s, W = np.linalg.eigh((B + B.T) / 2)             # ascending
    s, W = s[::-1][:r], W[:, ::-1][:, :r]
    good = s > rel_tol * max(s[0], 1e-30)
    inv = np.where(good, 1.0 / np.maximum(s, 1e-30), 0.0)
    return _small_eigs((W.T @ C @ W) * inv[None, :], good.sum())


def _no_basis(d: int, rank: int) -> jax.Array:
    """The basis argument of a cold ``_gram_operator`` call."""
    return jnp.zeros((d, min(2 * rank, d)), F32)


def gram_eigs(G: jax.Array, A: jax.Array, rank: int = 8,
              rel_tol: float = _REL_TOL) -> np.ndarray:
    """Eigenvalues of the online-DMD operator, rank-truncated.

    G = X Xᵀ (PSD), A = Y Xᵀ.  Project onto G's dominant eigenspace U_r
    (anything else is noise-nullspace and would blow up the pseudo-inverse):
    M_r = U_rᵀ A U_r diag(1/s_r);  eig(M_r).  Null directions come back NaN
    — consumers (metrics, tests) filter non-finite entries, so rank padding
    never reads as (in)stability.  Always the cold route (the full
    ``eigh``); ``StreamingDMD`` warm-starts from its previous solve."""
    B, C, *_ = _gram_operator(G, A, _no_basis(G.shape[0], rank),
                              jnp.bool_(False), rank=rank)
    return _ritz_eigs(B, C, rank, rel_tol)


def _pad_rows(n: int) -> int:
    """Round a batch size up to the next power of two so the jitted update
    compiles O(log n) variants instead of one per micro-batch size."""
    return 1 << max(0, n - 1).bit_length()


def _pad_cols(n: int, minimum: int = 4) -> int:
    """Power-of-two bucket for a pane's snapshot count (floor ``minimum``
    so the tiniest legal pane, 3 snapshots, shares a bucket with 4)."""
    return max(minimum, _pad_rows(n))


def _masked_window_operator(snaps: jax.Array, n_valid: jax.Array,
                            rank: int, rel_tol: float = _REL_TOL):
    """Windowed DMD reduction on a zero-padded (m, d) pane of snapshot
    rows, method of snapshots.  Returns (M (r, r), #good directions) for
    ``_small_eigs``.

    ``snaps`` holds ``n_valid`` real snapshot rows followed by zero
    padding; ``rank``/shapes are static, ``n_valid`` is data, so one
    compiled variant serves every pane in the same (m, d) bucket and the
    whole thing vmaps across panes.

    ``rel_tol`` applies to s² (the Gram eigenvalues); see ``_REL_TOL``.

    Exactness: with X = snaps[:n-1].T, Y = snaps[1:n].T, exact DMD's
    reduced operator is A~ = Uᵀ Y V S⁻¹ with X = U S Vᵀ.  Substituting
    Uᵀ = S⁻¹ Vᵀ Xᵀ gives A~' = S⁻¹ Vᵀ (XᵀY) V S⁻¹ — similar to A~ (same
    eigenvalues), and V/S² are the eigenvectors/eigenvalues of the small
    (m-1)² Gram XᵀX.  Zero feature columns change neither Gram; zero
    snapshot rows are removed by masking row ``n_valid - 1`` of X (the one
    padded position that holds real data) out of both Grams.  Spurious
    directions (beyond the pane's true pair count or below ``rel_tol``)
    are zeroed out of the operator — block-triangular, so they contribute
    exact-zero eigenvalues — which ``_small_eigs`` sorts last and masks to
    NaN, which consumers already filter.
    """
    with jax.default_matmul_precision(_PRECISION):
        m = snaps.shape[0]
        P = snaps @ snaps.T                       # (m, m) snapshot Gram
        lane = jnp.arange(m - 1)
        colmask = (lane < n_valid - 1).astype(F32)   # valid X columns
        mm = colmask[:, None] * colmask[None, :]
        G = P[:-1, :-1] * mm                      # XᵀX
        C = P[:-1, 1:] * mm                       # XᵀY
        s2, V = jnp.linalg.eigh(G)                # ascending
        r = min(rank, m - 1)
        s2_r = s2[-r:][::-1]                      # top-r, descending
        V_r = V[:, -r:][:, ::-1]
        good = ((jnp.arange(r) < n_valid - 1)
                & (s2_r > rel_tol * jnp.maximum(s2_r[0], 1e-30)))
        sinv = jnp.where(good, 1.0 / jnp.sqrt(jnp.maximum(s2_r, 1e-30)), 0.0)
        M = (V_r.T @ C @ V_r) * (sinv[:, None] * sinv[None, :])
        gm = good.astype(F32)
        M = M * (gm[:, None] * gm[None, :])
    return M, jnp.sum(good)


_window_operator = jax.jit(_masked_window_operator, static_argnames=("rank",))

# one vmapped+jitted reduction per rank (rank is a config constant in
# practice, so this dict stays O(1); the jit cache under each entry stays
# O(log) thanks to power-of-two (k, m) bucketing by the callers, and d is
# fixed by the configuration or bucketed too)
_BATCH_OPERATORS: dict[int, object] = {}


def _batched_operator(rank: int):
    fn = _BATCH_OPERATORS.get(rank)
    if fn is None:
        fn = jax.jit(jax.vmap(partial(_masked_window_operator, rank=rank)))
        _BATCH_OPERATORS[rank] = fn
    return fn


def _pane_rows(snapshots) -> list[np.ndarray]:
    return [np.asarray(s, np.float32).reshape(-1) for s in snapshots]


def _feature_width(pane_rows, n_features: int | None) -> int:
    """The slab's d: ``n_features`` as given (the configuration fixes it),
    else the longest row rounded up to a power of two, so that inputs of
    varying width reuse O(log) compiled variants."""
    if n_features is not None:
        return int(n_features)
    return _pad_rows(max((r.size for rows in pane_rows for r in rows),
                         default=1))


def _slab(pane_rows, k_pad: int, m_pad: int,
          d: int) -> tuple[np.ndarray, int]:
    """The (k_pad, m_pad, d) float32 slab of the panes' snapshot rows
    (``_pane_rows``), zero-padded, and the number of host copy calls that
    built it.

    Where every row is C-contiguous and exactly d floats long, one
    ``bytes.join`` gathers them, a shared zero row filling the padding:
    one copy call per slab, where a copy per row (or per pane) hands the
    interpreter lock to any busy thread thousands of times a solve and
    waits up to a switch interval to take it back each time.  Other rows
    are trimmed or zero-padded to d one at a time into the same layout."""
    rows = [r for p in pane_rows for r in p]
    if all(r.size == d and r.flags.c_contiguous for r in rows):
        zero = bytes(4 * d)
        parts = []
        for p in pane_rows:
            parts.extend(p)
            parts.extend([zero] * (m_pad - len(p)))
        parts.extend([zero] * (m_pad * (k_pad - len(pane_rows))))
        slab = np.frombuffer(b"".join(parts), np.float32)
        return slab.reshape(k_pad, m_pad, d), 1
    slab = np.zeros((k_pad, m_pad, d), np.float32)
    for slot, p in enumerate(pane_rows):
        for j, r in enumerate(p):
            w = min(r.size, d)
            slab[slot, j, :w] = r[:w]
    return slab, len(rows)


def window_dmd(snapshots, rank: int = 8,
               n_features: int | None = None) -> np.ndarray:
    """Batch DMD over one window pane — the stream-operator entry point.

    ``snapshots``: iterable of 1-D arrays (a fired window's values, e.g.
    record payloads in step order).  Each is flattened and trimmed /
    zero-padded to ``n_features`` (default: the longest snapshot, rounded
    up to a power of two).  The pane's rows are zero-padded to a
    power-of-two count before the masked solve, so sliding windows with
    ragged tails reuse O(log) compiled variants instead of one per pane
    size.  Windows shorter than 3 snapshots can't form a snapshot pair
    worth solving — returns the same zero sentinel
    ``StreamingDMD.eigenvalues`` uses.  Null/padded directions come back
    NaN; consumers filter non-finite entries."""
    rows = _pane_rows(snapshots)
    if len(rows) < 3:
        return np.zeros(1, np.complex64)
    m = len(rows)
    slab, _copies = _slab([rows], 1, _pad_cols(m),
                          _feature_width([rows], n_features))
    return _small_eigs(*_window_operator(jnp.asarray(slab[0]), jnp.int32(m),
                                         rank=rank))


def batched_window_dmd(panes, rank: int = 8,
                       n_features: int | None = None) -> list[np.ndarray]:
    """Multi-key windowed DMD: solve many co-fired panes in one dispatch.

    ``panes``: sequence of snapshot iterables (one fired pane per key /
    stream).  Panes are zero-padded into power-of-two (k, m) buckets of
    (k, m, d) row slabs, d as ``window_dmd`` takes it, and each bucket goes
    through one vmapped ``_masked_window_operator`` call — k ragged panes
    cost O(distinct m-buckets) dispatches instead of k.  Returns one
    eigenvalue array per pane, in input order; panes shorter than 3
    snapshots get the zero sentinel, padding slots inside a bucket are
    solved as empty panes and discarded.

    Spans (``repro.runtime.telemetry.span``): ``analysis.solve`` around the
    call; inside it ``analysis.fill`` (the panes' rows, then each bucket's
    slab: its bytes, the valid ones, and the host copy calls that built
    it), ``analysis.transfer`` (each slab's host→device copy and the
    solve's dispatch) and ``analysis.eig`` (each bucket's wait for the
    device, copy back and host eigensolve)."""
    with span("analysis.solve", panes=len(panes)) as solve_span:
        with span("analysis.fill"):
            pane_rows = [_pane_rows(p) for p in panes]
        out: list[np.ndarray | None] = [None] * len(pane_rows)
        d = _feature_width(pane_rows, n_features)
        buckets: dict[int, list[int]] = {}
        for i, rows in enumerate(pane_rows):
            if len(rows) < 3:
                out[i] = np.zeros(1, np.complex64)
            else:
                buckets.setdefault(_pad_cols(len(rows)), []).append(i)
        solve_span.set_metadata(snapshots=sum(
            len(pane_rows[i]) for idxs in buckets.values() for i in idxs))
        # fold buckets one power-of-two level apart into the wider one: the
        # masked solve makes extra column padding exactly invariant, and one
        # slightly wider slab beats a whole extra dispatch for the narrow
        # panes
        grouped: list[tuple[int, list[int]]] = []
        for mp in sorted(buckets, reverse=True):
            if grouped and mp * 2 >= grouped[-1][0]:
                grouped[-1][1].extend(buckets[mp])
            else:
                grouped.append((mp, list(buckets[mp])))
        solver = _batched_operator(rank)
        pending = []                          # dispatch all, then sync once
        for mp, idxs in grouped:
            kp = _pad_rows(len(idxs))
            with span("analysis.fill") as sp:
                slab, copies = _slab([pane_rows[i] for i in idxs], kp, mp, d)
                nv = np.zeros(kp, np.int32)   # padding panes solve as empty
                nv[: len(idxs)] = [len(pane_rows[i]) for i in idxs]
                sp.set_metadata(slab_bytes=slab.nbytes,
                                valid_bytes=4 * d * int(nv.sum()),
                                copies=copies)
            with span("analysis.transfer", bytes=slab.nbytes):
                pending.append(
                    (idxs, solver(jnp.asarray(slab), jnp.asarray(nv))))
        for idxs, (M, n_good) in pending:
            with span("analysis.eig", panes=len(idxs)):
                eigs = _small_eigs(M, n_good)
            for slot, i in enumerate(idxs):
                out[i] = eigs[slot]
    return out   # type: ignore[return-value]


def make_dmd_aggregate(rank: int = 8, n_features: int | None = None,
                       prepare=None):
    """Build the batch function for a ``BatchAggregate`` window consumer.

    Returns ``fn(items) -> list[np.ndarray]`` with ``items`` a list of
    ``(key, values)`` pairs (the BatchAggregate contract); ``prepare``
    (optional) maps a pane's value list to its snapshot iterable first
    (e.g. ``lambda vals: [r.payload for r in vals]``).  Co-fired panes
    across keys coalesce into one vmapped device dispatch — wire it as
    ``BatchAggregate("dmd", make_dmd_aggregate(...))``."""
    def batch_fn(items):
        with span("analysis.prepare", panes=len(items)):
            panes = [prepare(v) if prepare is not None else v
                     for _k, v in items]
        return batched_window_dmd(panes, rank=rank, n_features=n_features)
    return batch_fn


@dataclass
class StreamingDMD:
    """Per-stream online DMD state (executor-side), device-resident.

    ``use_kernel``: None = auto (fused Pallas kernel on TPU, jnp matmuls
    elsewhere — interpret-mode Pallas is not a hot-path option on CPU);
    True/False forces the choice (tests force True to exercise the kernel).
    ``donate``: donate G/A buffers into the jitted update so XLA reuses
    them in place (set False only when holding external references to the
    internal Gram arrays across updates).
    """

    n_features: int
    window: int = 32                 # snapshots kept for exact re-solves
    rank: int = 8
    use_kernel: bool | None = None
    donate: bool = True
    _buf: list = field(default_factory=list)
    _G: jax.Array | None = None      # (d, d) Gram, lives on device
    _A: jax.Array | None = None      # (d, d) cross-Gram, lives on device
    # the last Gram solve's top-p Ritz basis (d, p), on device: the next
    # solve's warm start
    _basis: jax.Array | None = None
    last_snapshot: np.ndarray | None = None
    n_seen: int = 0
    # eigensolve cache: valid until the next update lands
    _eigs_cache: np.ndarray | None = None
    _eigs_seen: int = -1             # n_seen at the time of the cached solve
    # hot-path accounting (BENCH_hotpath.json scoreboard)
    h2d_transfers: int = 0
    d2h_transfers: int = 0
    device_calls: int = 0
    warm_solves: int = 0             # Gram solves the warm start served
    eig_fallbacks: int = 0           # warm starts that fell back to eigh

    def _coerce(self, snapshot) -> np.ndarray:
        x = np.asarray(snapshot, np.float32).reshape(-1)[: self.n_features]
        if x.size < self.n_features:   # short payloads embed zero-padded
            x = np.pad(x, (0, self.n_features - x.size))
        return x

    def _coerce_block(self, snaps) -> np.ndarray:
        """(n, d) float32 block from any snapshot batch.  A 2-D ndarray of
        matching width takes the no-copy fast path — the per-row python
        loop is what BENCH_hotpath's update_only section times at d=512."""
        if isinstance(snaps, np.ndarray) and snaps.ndim == 2:
            arr = snaps.astype(np.float32, copy=False)
            d = self.n_features
            if arr.shape[1] > d:
                arr = arr[:, :d]
            elif arr.shape[1] < d:
                arr = np.pad(arr, ((0, 0), (0, d - arr.shape[1])))
            return arr
        rows = [self._coerce(s) for s in snaps]
        if not rows:
            return np.empty((0, self.n_features), np.float32)
        return np.stack(rows)

    def _apply_pair_block(self, X: np.ndarray, Y: np.ndarray) -> None:
        """One device call: G += XᵀX, A += YᵀX for an (n, d) pair block."""
        d = self.n_features
        if self._G is None:
            self._G = jnp.zeros((d, d), F32)
            self._A = jnp.zeros((d, d), F32)
        Xd, Yd = jnp.asarray(X), jnp.asarray(Y)
        self.h2d_transfers += 2
        self.device_calls += 1
        use_kernel = (self.use_kernel if self.use_kernel is not None
                      else jax.default_backend() == "tpu")
        if use_kernel:
            from repro.kernels import ops
            fn = (ops.gram_pair_accumulate_donated if self.donate
                  else ops.gram_pair_accumulate)
            self._G, self._A = fn(Xd, Yd, self._G, self._A)
        else:
            fn = gram_pair_update_donated if self.donate else gram_pair_update
            self._G, self._A = fn(self._G, self._A, Xd, Yd)

    def update(self, snapshot: np.ndarray) -> None:
        """Single-snapshot update (legacy per-record path)."""
        self.update_batch([snapshot])

    def update_batch(self, snaps) -> None:
        """Batched update: ``snaps`` is an (n, d) array or list of snapshots
        (each trimmed/zero-padded to ``n_features``).  Forms the shifted
        X = chain[:-1], Y = chain[1:] pair — chaining through the previous
        batch's last snapshot — and applies it in one device call.  Span
        ``analysis.stream_update``: the snapshots given (``rows``), the pair
        rows shipped after padding (``padded_rows``) and the bytes of X and
        Y sent to the device (``bytes``)."""
        block = self._coerce_block(snaps)
        if block.shape[0] == 0:
            return
        with span("analysis.stream_update", rows=block.shape[0]) as sp:
            if self.last_snapshot is not None:
                chain = np.concatenate([self.last_snapshot[None], block])
            else:
                chain = block
            X, Y = chain[:-1], chain[1:]
            n = X.shape[0]
            m = _pad_rows(n) if n else 0
            if n:
                if m != n:   # zero rows contribute nothing to XᵀX / YᵀX
                    pad = np.zeros((m - n, self.n_features), np.float32)
                    X = np.concatenate([X, pad])
                    Y = np.concatenate([Y, pad])
                self._apply_pair_block(X, Y)
            sp.set_metadata(padded_rows=m, bytes=2 * m * self.n_features * 4)
            self.last_snapshot = np.ascontiguousarray(chain[-1])
            self._buf.extend(block)
            del self._buf[: max(0, len(self._buf) - self.window)]
            self.n_seen += block.shape[0]

    def eigenvalues(self) -> np.ndarray:
        """Current DMD eigenvalues.  Cached: a second call with no update
        in between returns the previous solve without touching the device
        (telemetry re-reads stop re-running the solve on unchanged G/A —
        watch ``device_calls`` stand still).  Span ``analysis.stream_eig``:
        the route taken (``cached``; ``exact`` while the stream has seen at
        most ``window`` snapshots; past that ``warm``, subspace iteration
        from the previous Gram solve's basis, or ``gram``, the full
        ``eigh``: the first Gram solve and any warm start that fell back),
        ``n_seen``, and on the Gram route ``iters`` (warm iterations run)
        and ``fell_back``."""
        with span("analysis.stream_eig", n_seen=self.n_seen) as sp:
            if (self._eigs_cache is not None
                    and self._eigs_seen == self.n_seen):
                sp.set_metadata(route="cached")
                return self._eigs_cache
            route = "exact"
            if self.n_seen < 3:
                eigs = np.zeros(1, np.complex64)
            elif self.n_seen <= self.window:
                snaps = jnp.asarray(np.stack(self._buf, axis=1))
                self.h2d_transfers += 1
                self.device_calls += 1
                eigs, _ = exact_dmd(snaps, rank=self.rank)
                self.d2h_transfers += 1
            else:
                eigs, route, iters, fell_back = self._gram_solve()
                sp.set_metadata(iters=iters, fell_back=fell_back)
            sp.set_metadata(route=route)
            self._eigs_cache = eigs
            self._eigs_seen = self.n_seen
            return eigs

    def _gram_solve(self) -> tuple[np.ndarray, str, int, bool]:
        """One ``_gram_operator`` call, warm from the previous Gram solve's
        basis where there is one; keeps the new basis on the device.
        Returns (eigenvalues, route, warm iterations run, fell back)."""
        warm = self._basis is not None
        basis = self._basis if warm else _no_basis(self.n_features,
                                                   self.rank)
        self.device_calls += 1
        B, C, self._basis, iters, fell_back = _gram_operator(
            self._G, self._A, basis, jnp.bool_(warm), rank=self.rank)
        B, C, iters, fell_back = jax.device_get((B, C, iters, fell_back))
        self.d2h_transfers += 1
        fell_back = bool(fell_back)
        self.eig_fallbacks += fell_back
        self.warm_solves += warm and not fell_back
        route = "warm" if warm and not fell_back else "gram"
        return _ritz_eigs(B, C, self.rank), route, int(iters), fell_back
