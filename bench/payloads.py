"""Seeded synthetic field snapshots: the payload model of ``sim/synthetic.py``.

A low-rank linear dynamical system, so downstream DMD finds real
eigenstructure: ``modes`` damped oscillators (rotation θ ~ U(0.05, 0.3),
decay ~ U(0.97, 1.0) per step) mixed into ``d`` floats by a fixed
(d, 2·modes) matrix of N(0, 0.25) entries, rank r's phase offset by 0.37·r,
plus ``noise`` × N(0, 1) per float.  Unlike ``sim/synthetic.py``, every
random draw comes from the run's seed, and the snapshots are made in bulk
before the measured window: a pool of ``steps`` snapshots per rank, which
the generator cycles through.
"""
from __future__ import annotations

import numpy as np


def snapshot_pool(seed: int, ranks: int, steps: int, d: int, *,
                  modes: int = 3, noise: float = 0.01) -> np.ndarray:
    """(ranks, steps, d) float32 snapshots for step 0..steps-1 of each rank."""
    rng = np.random.default_rng(seed)
    theta = rng.uniform(0.05, 0.3, size=modes)
    decay = rng.uniform(0.97, 1.0, size=modes)
    mix = (rng.standard_normal((d, 2 * modes)) * 0.5).astype(np.float32)
    t = (np.arange(steps)[None, :] + 0.37 * np.arange(ranks)[:, None])[..., None]
    amp = decay ** t                                         # (ranks, steps, modes)
    z = np.concatenate([amp * np.cos(theta * t), amp * np.sin(theta * t)],
                       axis=-1).astype(np.float32)           # (ranks, steps, 2m)
    out = z @ mix.T                                          # (ranks, steps, d)
    out += noise * rng.standard_normal(out.shape, dtype=np.float32)
    return out
