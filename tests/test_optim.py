"""Optimizer: AdamW math vs numpy reference, q8 moment error bounds,
schedule shape."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.optim import adamw


def _np_adamw_step(p, g, m, v, step, cfg):
    m = cfg.b1 * m + (1 - cfg.b1) * g
    v = cfg.b2 * v + (1 - cfg.b2) * g * g
    bc1 = 1 - cfg.b1 ** step
    bc2 = 1 - cfg.b2 ** step
    u = (m / bc1) / (np.sqrt(v / bc2) + cfg.eps)
    return m, v, u


def test_adamw_matches_numpy_reference():
    cfg = adamw.AdamWConfig(lr=1e-2, weight_decay=0.0, grad_clip=1e9,
                            warmup_steps=0, total_steps=10**9)
    params = {"w": jnp.asarray(np.ones((4, 4), np.float32))}
    grads = {"w": jnp.asarray(np.full((4, 4), 0.5, np.float32))}
    opt = adamw.init_opt_state(cfg, params)
    p2, opt2, _ = adamw.apply_updates(cfg, params, grads, opt)
    m, v, u = _np_adamw_step(np.ones((4, 4)), np.full((4, 4), 0.5),
                             np.zeros((4, 4)), np.zeros((4, 4)), 1, cfg)
    # schedule at step 1 with warmup 0: cosine at t=1/total ~ lr
    lr = float(adamw.cosine_schedule(cfg.lr, 0, cfg.total_steps)(jnp.asarray(1)))
    want = np.ones((4, 4)) - lr * u
    np.testing.assert_allclose(np.asarray(p2["w"]), want, rtol=1e-5)


def test_grad_clip_applies():
    cfg = adamw.AdamWConfig(grad_clip=1.0)
    params = {"w": jnp.zeros((8,), jnp.float32)}
    grads = {"w": jnp.full((8,), 100.0)}
    opt = adamw.init_opt_state(cfg, params)
    _, _, metrics = adamw.apply_updates(cfg, params, grads, opt)
    assert float(metrics["grad_norm"]) > 100


@given(st.integers(1, 6))
@settings(max_examples=10, deadline=None)
def test_8bit_tracks_fp32(seed):
    rng = np.random.RandomState(seed)
    p0 = {"w": jnp.asarray(rng.randn(16, 256).astype(np.float32))}
    cfg32 = adamw.AdamWConfig(use_8bit=False, weight_decay=0.0)
    cfg8 = adamw.AdamWConfig(use_8bit=True, weight_decay=0.0)
    o32 = adamw.init_opt_state(cfg32, p0)
    o8 = adamw.init_opt_state(cfg8, p0)
    p32, p8 = p0, p0
    for step in range(3):
        g = {"w": jnp.asarray(rng.randn(16, 256).astype(np.float32))}
        p32, o32, _ = adamw.apply_updates(cfg32, p32, g, o32)
        p8, o8, _ = adamw.apply_updates(cfg8, p8, g, o8)
    diff = np.abs(np.asarray(p32["w"]) - np.asarray(p8["w"]))
    scale = np.abs(np.asarray(p32["w"]) - np.asarray(p0["w"])).max()
    assert diff.max() <= 0.35 * scale + 1e-5  # 8-bit drift bounded vs update size


@given(st.sampled_from([(4, 8), (3, 256), (16, 128), (2, 1000)]))
@settings(max_examples=20, deadline=None)
def test_q8_roundtrip_bound(shape):
    rng = np.random.RandomState(shape[1])
    x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 10)
    z = adamw.q8_encode(x)
    back = adamw.q8_decode(z)
    q = adamw.block_size(shape[-1])
    blocks = np.asarray(x).reshape(*shape[:-1], shape[-1] // q, q)
    bound = np.abs(blocks).max(-1, keepdims=True) / 127 * 0.51 + 1e-7
    err = np.abs(np.asarray(back).reshape(blocks.shape) - blocks)
    assert np.all(err <= bound)


def test_schedule_warmup_and_decay():
    lr = adamw.cosine_schedule(1e-3, warmup=100, total=1000)
    assert float(lr(jnp.asarray(0))) == 0.0
    assert float(lr(jnp.asarray(50))) < float(lr(jnp.asarray(100)))
    assert abs(float(lr(jnp.asarray(100))) - 1e-3) < 1e-9
    assert float(lr(jnp.asarray(1000))) < float(lr(jnp.asarray(500)))
    assert float(lr(jnp.asarray(1000))) >= 1e-4 - 1e-9   # min_ratio floor
