import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, ``jax.jit(step).lower(...)
.compile()`` must succeed on the 16x16 single-pod mesh AND the 2x16x16
multi-pod mesh.  Dumps memory_analysis + cost_analysis + the per-collective
byte census (parsed from the optimized HLO) to artifacts/dryrun/*.json — the
roofline analysis (benchmarks/roofline_table.py, EXPERIMENTS.md) reads these.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, get_config, list_archs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_spec
from repro.roofline.analysis import collective_bytes_from_hlo, roofline_terms
from repro.roofline.jaxpr_flops import program_counts

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             force: bool = False, layout: str = "tp",
             no_remat: bool = False) -> dict:
    import dataclasses
    from repro.parallel.sharding import recommended_layout, set_layout
    cfg = get_config(arch)
    shape = next(s for s in SHAPES if s.name == shape_name)
    if layout == "auto":
        layout = recommended_layout(cfg, shape)
    set_layout(layout)
    if no_remat:
        cfg = dataclasses.replace(cfg, remat=False)
    suffix = ("" if layout == "tp" else f"__{layout}") + \
        ("__noremat" if no_remat else "")
    out = out_dir / f"{arch}__{shape_name}__{mesh_kind}{suffix}.json"
    if out.exists() and not force:
        return json.loads(out.read_text())

    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "layout": layout}
    if not ok:
        rec.update(status="skipped", reason=why)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rec, indent=1))
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        spec = make_spec(cfg, shape, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(spec.fn).lower(*spec.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo)
        n_dev = mesh.devices.size
        # XLA:CPU cost_analysis does not multiply while-bodies by trip count,
        # so the authoritative FLOP/byte numbers come from the jaxpr walker
        # (global/logical); cost_analysis values are recorded alongside.
        prog = program_counts(spec.fn, *spec.args)
        top_prims = dict(sorted(prog.by_prim.items(),
                                key=lambda kv: -kv[1][0])[:12])
        xla_flops = float(cost.get("flops", 0.0))
        xla_bytes = float(cost.get("bytes accessed", 0.0))
        rec.update(
            status="ok",
            n_devices=int(n_dev),
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            program_flops=prog.flops,           # global, trip-counted
            program_bytes=prog.bytes,           # global, un-fused upper bound
            program_top_prims=top_prims,
            xla_flops_per_device=xla_flops,
            xla_bytes_per_device=xla_bytes,
            collectives=coll,                   # per-device traffic estimate
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            model_params=cfg.n_params(),
            model_active_params=cfg.n_active_params(),
            roofline=roofline_terms(
                flops=prog.flops,
                hlo_bytes=xla_bytes * n_dev,
                collective_bytes=coll["total_bytes"] * n_dev,
                n_devices=n_dev, cfg=cfg, shape=shape),
        )
    # reprolint: allow(loud-corruption) — a failing sweep cell is a result to record, not a crash: the error and traceback land in the cell artifact
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--layout", default="tp", choices=["tp", "fsdp", "dp", "ep", "auto"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(ART))
    args = ap.parse_args()
    out_dir = Path(args.out)

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, mesh_kind, out_dir,
                               force=args.force, layout=args.layout,
                               no_remat=args.no_remat)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f"compile={rec['compile_s']}s "
                             f"pflops={rec['program_flops']:.3g} "
                             f"coll={rec['collectives']['total_bytes']:.3g}B "
                             f"dom={rec['roofline']['dominant']}")
                elif status == "error":
                    extra = rec["error"][:160]
                    failures += 1
                print(f"[{mesh_kind:6s}] {arch:24s} {shape_name:12s} "
                      f"{status:8s} {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
