"""Multi-pod dry-run driver.

For each (arch x shape x mesh) cell: lower the real step function against
abstract sharded inputs, ``.compile()`` it, record ``memory_analysis()`` /
``cost_analysis()``, and run the per-device HLO roofline extractor
(``repro.launch.hlo_analysis``).  Artifacts land in
``benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>.json`` (+ zstd HLO text
for offline re-analysis during perf iterations).

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all
"""
from __future__ import annotations

import os
# MUST precede any jax import: jax locks the device count on first init.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro import configs
from repro.configs.base import SHAPES
from repro.launch import hlo_analysis
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import make_constrain
from repro.models.steps import step_for_shape

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path = DEFAULT_OUT, save_hlo: bool = True,
             tag: str = "", cfg_override=None) -> dict:
    cfg = cfg_override or configs.get(arch)
    cells = {s.name: s for s in cfg.shape_cells()}
    if shape_name not in cells:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "long_500k requires sub-quadratic attention"}
    shape = cells[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    n_dev = mesh.size

    t0 = time.time()
    step = step_for_shape(cfg, shape, constrain=make_constrain(mesh))
    args = input_specs(cfg, shape, mesh)
    donate = (0, 1) if shape.kind == "train" else ((1,) if shape.kind == "decode" else ())
    with mesh:
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    analysis = hlo_analysis.analyze(text)
    model_flops = cfg.model_flops(shape) / n_dev
    terms = hlo_analysis.roofline_terms(analysis, model_flops)
    # TPU-adjusted: Pallas-kernel regions fused + CPU bf16-legalization undone
    adjusted = hlo_analysis.tpu_dtype_corrected(
        hlo_analysis.kernelized(analysis),
        grad_dtype_f32=(shape.kind == "train" and not cfg.opt_8bit))
    terms_kernel = hlo_analysis.roofline_terms(adjusted, model_flops)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok",
        "kind": shape.kind,
        "microbatches": shape.microbatches,
        "n_devices": n_dev,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
        },
        "xla_cost_analysis": {k: v for k, v in cost.items()
                              if k in ("flops", "bytes accessed")},
        "hlo_analysis": analysis,
        "roofline": terms,
        "roofline_kernelized": terms_kernel,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    stem = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    (out_dir / f"{stem}.json").write_text(json.dumps(result, indent=1))
    if save_hlo:
        import zstandard
        (out_dir / f"{stem}.hlo.zst").write_bytes(
            zstandard.ZstdCompressor(level=3).compress(text.encode()))
    return result


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", help="architecture id (see repro.configs)")
    p.add_argument("--shape", help="shape cell name", default=None)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true", help="all cells, both meshes")
    p.add_argument("--out", default=str(DEFAULT_OUT))
    p.add_argument("--no-hlo", action="store_true")
    p.add_argument("--tag", default="", help="artifact suffix (perf iterations)")
    args = p.parse_args()
    out = Path(args.out)

    cells: list[tuple[str, str, bool]] = []
    if args.all:
        for arch in configs.list_archs():
            for s in configs.get(arch).shape_cells():
                cells.append((arch, s.name, False))
                cells.append((arch, s.name, True))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch} x {shape} x {'multipod' if mp else 'pod'}"
        try:
            r = run_cell(arch, shape, mp, out, save_hlo=not args.no_hlo,
                         tag=args.tag)
            rf = r.get("roofline", {})
            print(f"[dryrun] OK {tag}: bound={rf.get('bound')} "
                  f"compute={rf.get('compute_s', 0):.4f}s "
                  f"mem={rf.get('memory_s', 0):.4f}s "
                  f"coll={rf.get('collective_s', 0):.4f}s "
                  f"compile={r.get('compile_s')}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"[dryrun] FAIL {tag}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
