import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: .lower().compile() every (architecture x input-shape x
mesh) combination with ShapeDtypeStruct inputs (no allocation), record
memory_analysis / cost_analysis / parsed collective schedule, and emit the
roofline artifact JSON that EXPERIMENTS.md §Dry-run and §Roofline read.

NOTE: the XLA_FLAGS line above MUST stay the first statement — jax locks the
device count at first init. The flag lives only in this module (and the
subprocesses benchmarks spawn); tests and benches see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ASSIGNED, get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as STEPS
from repro.launch.roofline import parse_hlo_collectives, build_report

SHAPES = list(STEPS.INPUT_SHAPES)
# the chip the production meshes stand for: the forced host devices compile
# the program, the roofline terms use this kind's published peaks
TARGET_KIND = "TPU v5 lite"


def normalize_cost_analysis(cost) -> dict:
    """``compiled.cost_analysis()`` as a plain dict — the installed JAX
    returns one dict per executable, or None when the backend reports no
    cost — so ``cost.get("flops")`` always works."""
    return dict(cost or {})


def run_one(arch: str, shape_name: str, mesh_name: str, *, out_dir=None,
            verbose=True, hlo_dir=None, variant="base"):
    cfg = get_config(arch)
    if not STEPS.supports(cfg, shape_name):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "variant": variant,
               "reason": "requires sub-quadratic attention (DESIGN.md §4)"}
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            suffix = "" if variant == "base" else f"_{variant}"
            path = os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_name}{suffix}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.devices.size
    model_shards = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_chips": n_chips, "status": "ok", "variant": variant}
    try:
        built = STEPS.build_step(arch, shape_name, mesh, variant=variant)
        jitted, sds_args, cfg, kind = built
        rec["step_kind"] = kind
        with mesh:
            lowered = jitted.lower(*sds_args)
            t_low = time.time()
            compiled = lowered.compile()
            t_comp = time.time()
        ma = compiled.memory_analysis()
        cost = normalize_cost_analysis(compiled.cost_analysis())
        hlo = compiled.as_text()
        coll = parse_hlo_collectives(
            hlo, bf16_dot_comms=(cfg.compute_dtype == "bfloat16"))
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(
                    hlo_dir, f"{arch}_{shape_name}_{mesh_name}.hlo"), "w") as f:
                f.write(hlo)
        rec.update(
            lower_s=round(t_low - t0, 2), compile_s=round(t_comp - t_low, 2),
            memory={
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
            },
            cost={"flops": cost.get("flops"),
                  "bytes_accessed": cost.get("bytes accessed")},
            collectives=coll,
        )
        fcfg = STEPS.default_favas_config(mesh)
        report = build_report(
            arch, shape_name, mesh_name, cfg, STEPS.INPUT_SHAPES[shape_name],
            n_chips, model_shards, cost, coll,
            local_steps=fcfg.R if kind == "train" else 0,
            param_bytes=4 if kind == "train" else 2, device_kind=TARGET_KIND)
        rec["roofline"] = {
            "compute_s": report.compute_s, "memory_s": report.memory_s,
            "collective_s": report.collective_s, "dominant": report.dominant,
            "model_flops": report.model_flops,
            "useful_ratio": report.useful_ratio,
            "raw_cost_flops": report.raw_cost_flops,
        }
        if verbose:
            print(f"[ok] {arch} x {shape_name} x {mesh_name}: "
                  f"lower {rec['lower_s']}s compile {rec['compile_s']}s | "
                  f"temp {rec['memory']['temp_bytes']} B | "
                  f"coll {coll['total_bytes']:.3e} B | dom {report.dominant}")
            print("     memory_analysis:", ma)
            print("     cost_analysis: flops=%s bytes=%s" %
                  (cost.get("flops"), cost.get("bytes accessed")))
    except Exception as e:  # noqa: BLE001 — record the failure, don't die
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[ERR] {arch} x {shape_name} x {mesh_name}: {rec['error']}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if variant == "base" else f"_{variant}"
        path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (see configs)")
    ap.add_argument("--shape", default=None, choices=SHAPES)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="all assigned archs x all shapes")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--variant", default="base", choices=["base", "opt"])
    args = ap.parse_args()

    archs = ASSIGNED if args.all or not args.arch else [args.arch]
    shapes = SHAPES if args.all or not args.shape else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for mesh_name in meshes:
        for arch in archs:
            for shape in shapes:
                results.append(run_one(arch, shape, mesh_name, out_dir=args.out,
                                       hlo_dir=args.hlo_dir,
                                       variant=args.variant))
    ok = sum(r["status"] == "ok" for r in results)
    skip = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"\n=== dry-run summary: {ok} ok / {skip} skipped / {err} errors "
          f"of {len(results)} ===")
    for r in results:
        if r["status"] == "error":
            print("  FAILED:", r["arch"], r["shape"], r["mesh"], "->", r["error"])
    return 0 if err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
