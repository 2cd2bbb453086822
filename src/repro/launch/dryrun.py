import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh(es) and extract memory / cost / collective-schedule data.

The two lines above MUST stay the first statements of this module — jax
locks the device count at first init, and only the dry-run is allowed to
see 512 placeholder devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                   # everything
  ... --arch gemma3-12b --shape train_4k --mesh single           # one cell
  ... --reduced --devices 4                                      # CI smoke
  ... --out experiments/dryrun                                   # JSON dir

Each cell writes ``<out>/<arch>__<shape>__<mesh>.json`` containing
memory_analysis, cost_analysis FLOPs/bytes, per-kind collective bytes and
the derived roofline terms (consumed by benchmarks/roofline_table.py and
EXPERIMENTS.md).
"""
import argparse
import json
import pathlib
import sys
import time
import traceback


def _build_mesh(which: str, reduced_devices: int | None):
    import jax
    from repro.launch.mesh import make_mesh, make_production_mesh

    if reduced_devices:
        if which == "multi":
            return make_mesh((2, reduced_devices // 4, 2), ("pod", "data", "model")), f"{2}x{reduced_devices//4}x2"
        return make_mesh((reduced_devices // 2, 2), ("data", "model")), f"{reduced_devices//2}x2"
    if which == "multi":
        return make_production_mesh(multi_pod=True), "2x16x16"
    return make_production_mesh(multi_pod=False), "16x16"


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             out_dir: pathlib.Path, reduced: bool = False,
             reduced_devices: int | None = None,
             fused_attn: bool = False) -> dict:
    import jax
    from repro.configs import SHAPES, applicable, get_config
    from repro.optim.adamw import AdamWConfig
    from repro.roofline import analysis as roof
    from repro.runtime import steps

    cfg = get_config(arch_name)
    shape = SHAPES[shape_name]
    if reduced:
        cfg = cfg.reduced()
        shape = shape.reduced()
    ok, reason = applicable(get_config(arch_name), SHAPES[shape_name])
    rec: dict = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
        "reduced": reduced,
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    mesh, mesh_desc = _build_mesh(mesh_kind, reduced_devices)
    rec["mesh_desc"] = mesh_desc
    rec["fused_attn"] = fused_attn
    n_dev = mesh.size
    t0 = time.time()
    import contextlib

    from repro.models.attention import flash_fusion

    fuse_ctx = flash_fusion(True) if fused_attn else contextlib.nullcontext()
    with mesh, fuse_ctx:
        lowered = steps.lower_cell(cfg, shape, mesh, AdamWConfig())
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k, 0) or 0)
            for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
        }
        per_dev_bytes = (
            rec["memory_analysis"]["argument_size_in_bytes"]
            + rec["memory_analysis"]["temp_size_in_bytes"]
            + rec["memory_analysis"]["output_size_in_bytes"]
            - rec["memory_analysis"]["alias_size_in_bytes"]
        )
        rec["per_device_peak_bytes"] = int(per_dev_bytes)

        # XLA's module-level cost_analysis counts while bodies once — keep
        # it for reference, but use the loop-aware HLO cost model for the
        # roofline terms (see roofline/hlocost.py).
        cost = compiled.cost_analysis()
        rec["cost_analysis_xla"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        from repro.roofline import hlocost

        hlo = compiled.as_text()
        lac = hlocost.analyze(hlo)
        flops = lac.flops
        # roofline memory term uses the ideal-fusion bound (TPU target
        # fuses elementwise chains the CPU-backend HLO leaves unfused);
        # the pessimistic unfused number is kept alongside.
        bytes_accessed = lac.bytes_min
        rec["cost_analysis"] = {
            "flops": flops, "bytes_min": lac.bytes_min,
            "bytes_unfused": lac.bytes_accessed,
            "dot_flops": lac.dot_flops,
            "elementwise_flops": lac.elementwise_flops,
        }
        pod_stride = 256 if mesh_kind == "multi" else 1 << 30
        coll = roof.collectives_from_ops(
            lac.collective_ops, n_dev, pod_stride=pod_stride
        )

    from repro.models.model import build_model
    from repro.runtime.steps import abstract_params

    aparams = abstract_params(build_model(cfg))
    n_params = roof.count_params(aparams)
    act = roof.active_params(cfg, aparams)
    rec["n_params"] = n_params
    rec["active_params"] = act
    mf = roof.model_flops_estimate(cfg, shape, n_params, act)

    rl = roof.Roofline(
        arch=arch_name, shape=shape_name, mesh=mesh_desc, n_devices=n_dev,
        hlo_gflops=flops / 1e9, hlo_bytes=bytes_accessed, coll=coll,
        model_flops=mf, per_device_peak_bytes=rec["per_device_peak_bytes"],
    )
    rec.update(rl.to_json())
    rec["status"] = "ok"
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch id (default: all)")
    ap.add_argument("--shape", default=None, help="single shape (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--reduced", action="store_true", help="CI smoke mode")
    ap.add_argument("--devices", type=int, default=None,
                    help="reduced device count (with --reduced)")
    ap.add_argument("--fused-attn", action="store_true",
                    help="model the flash-attention Pallas kernel in the "
                         "roofline (fused_kernel region accounting)")
    args = ap.parse_args(argv)

    from repro.configs import ALL_SHAPES, ASSIGNED_ARCHS

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else [s.name for s in ALL_SHAPES]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape}__{mesh_kind}"
                if args.fused_attn:
                    tag += "__fused"
                path = out_dir / f"{tag}.json"
                try:
                    rec = run_cell(arch, shape, mesh_kind, out_dir,
                                   reduced=args.reduced,
                                   reduced_devices=args.devices,
                                   fused_attn=args.fused_attn)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "status": "error", "error": repr(e),
                        "traceback": traceback.format_exc(),
                    }
                    failures += 1
                path.write_text(json.dumps(rec, indent=2))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (
                        f" compile={rec.get('compile_s')}s"
                        f" mem/dev={rec.get('per_device_peak_bytes', 0)/2**30:.2f}GiB"
                        f" bottleneck={rec.get('bottleneck')}"
                    )
                elif status == "skipped":
                    extra = f" ({rec['reason']})"
                else:
                    extra = f" {rec['error']}"
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    if failures:
        print(f"[dryrun] {failures} cell(s) FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
