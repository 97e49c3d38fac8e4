"""int8 cross-pod gradient reduction: correctness in subprocess (multi-device)
and error-feedback unbiasedness in-process."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.compress import ErrorFeedback, _q8_flat, _dq8_flat

REPO = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.optim.compress import compressed_pod_mean

mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.RandomState(0)
g_np = rng.randn(2, 64, 32).astype(np.float32)  # leading dim = per-pod grads
g = jax.device_put(jnp.asarray(g_np),
                   NamedSharding(mesh, P()))  # replicated input per device

# fake per-pod partials: pod p sees g * (p+1)
def per_pod(local):
    idx = jax.lax.axis_index("pod").astype(jnp.float32)
    return local * (idx + 1.0)

from functools import partial
@partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
         check_vma=False)
def make_partials(x):
    return per_pod(x)

partials = make_partials(g)
out = compressed_pod_mean({"w": partials}, mesh)["w"]
want = g_np * 1.5  # mean of 1x and 2x
err = float(np.max(np.abs(np.asarray(out) - want)))
rel = err / float(np.abs(want).max())
print(json.dumps({"rel_err": rel}))
"""


def test_compressed_pod_mean_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": str(REPO / "src"),
                            "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"}, cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["rel_err"] < 0.02  # int8 blockwise error bound


def test_roundtrip_and_error_feedback():
    rng = np.random.RandomState(1)
    g = {"w": jnp.asarray(rng.randn(1000).astype(np.float32))}
    res = ErrorFeedback.init(g)
    # accumulate many steps of the SAME gradient: with error feedback the
    # mean of sent values converges to the true gradient
    sent_sum = np.zeros(1000, np.float32)
    for i in range(20):
        sent, res = ErrorFeedback.apply(g, res)
        sent_sum += np.asarray(sent["w"])
    mean_sent = sent_sum / 20
    raw_q = _dq8_flat(*_q8_flat(g["w"]), g["w"].shape)
    err_ef = np.abs(mean_sent - np.asarray(g["w"])).max()
    err_raw = np.abs(np.asarray(raw_q) - np.asarray(g["w"])).max()
    assert err_ef <= err_raw + 1e-7
    assert err_ef < 0.01 * np.abs(np.asarray(g["w"])).max()
