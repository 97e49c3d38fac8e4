"""Elastic restart: a checkpoint saved under one mesh restores onto a
different mesh shape (subprocess with 8 forced host devices)."""
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.ckpt import CheckpointManager

mesh_a = jax.make_mesh((2, 4), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh_b = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.RandomState(0)
w_np = rng.randn(16, 32).astype(np.float32)
w_a = jax.device_put(jnp.asarray(w_np),
                     NamedSharding(mesh_a, P("data", "model")))

d = tempfile.mkdtemp()
mgr = CheckpointManager(d)
mgr.save(5, {"w": w_a}, blocking=True)

# restore onto mesh_b with transposed parallelism
target = jax.ShapeDtypeStruct((16, 32), jnp.float32,
                              sharding=NamedSharding(mesh_b, P("data", "model")))
tree, step = mgr.restore({"w": target})
w_b = tree["w"]
ok_value = bool(np.array_equal(np.asarray(w_b), w_np))
shard_shapes = sorted({tuple(s.data.shape) for s in w_b.addressable_shards})
print(json.dumps({"step": step, "ok_value": ok_value,
                  "shard_shapes": [list(s) for s in shard_shapes]}))
"""


def test_restore_onto_different_mesh():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": str(REPO / "src"),
                            "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"}, cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["step"] == 5
    assert out["ok_value"]
    # mesh_b shards: (16/4, 32/2) = (4, 16) — proves real resharding happened
    assert out["shard_shapes"] == [[4, 16]]
