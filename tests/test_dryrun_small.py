"""Dry-run machinery integration test on a tiny forced-device mesh.

Runs in a subprocess because jax locks the device count at first init; the
main pytest process must keep seeing 1 CPU device.
"""
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import repro.configs as C
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_test_mesh
from repro.launch.shardings import make_constrain
from repro.launch import hlo_analysis
from repro.launch.inputs import input_specs
from repro.models.steps import step_for_shape

mesh = make_test_mesh((2, 4), ("data", "model"))
out = {}
for arch in ["starcoder2-3b", "jamba-1.5-large-398b"]:
    cfg = C.get(arch).reduced()
    # pad dims so the (2,4) mesh divides them
    from dataclasses import replace
    cfg = replace(cfg, d_model=128, d_ff=256, vocab_size=512)
    for shape in [ShapeConfig("t", 64, 8, "train", 2),
                  ShapeConfig("d", 64, 8, "decode")]:
        step = step_for_shape(cfg, shape, constrain=make_constrain(mesh))
        args = input_specs(cfg, shape, mesh)
        with mesh:
            lowered = jax.jit(step).lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        a = hlo_analysis.analyze(compiled.as_text())
        out[f"{arch}/{shape.kind}"] = {
            "flops": a["flops"],
            "collectives": {k: v for k, v in a["collectives"].items() if v},
            "arg_bytes": mem.argument_size_in_bytes,
        }
print(json.dumps(out))
"""


def test_tiny_mesh_dryrun():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out) == 4
    for cell, info in out.items():
        assert info["flops"] > 0, cell
        assert info["arg_bytes"] > 0, cell
    # sharded training must communicate
    assert any(info["collectives"] for info in out.values())
