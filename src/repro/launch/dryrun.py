import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first backend init). 512 placeholder host devices let
# jax.make_mesh build the production pod meshes; nothing is allocated —
# every program is lowered from ShapeDtypeStructs and AOT-compiled only.
"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) combo.

For each combo this records, into benchmarks/artifacts/dryrun/:
  * memory_analysis()  — per-device argument/output/temp bytes (proves fit),
  * cost_analysis()    — per-device HLO FLOPs + bytes accessed,
  * collective_bytes   — sum of per-device payload bytes over every
    all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute in the post-SPMD optimized HLO,
  * the roofline terms derived from the above.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.models.arch import get_arch, list_archs
from .mesh import make_production_mesh
from .shapes import SHAPES
from . import steps as S
from . import hlo_analysis as H

ARTIFACT_DIR = os.path.join("benchmarks", "artifacts", "dryrun")
PRINT_BUFFERS = False


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            *, fsdp: bool = True, tag: str = "", microbatches: int = 0) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    prog = S.build_program(cfg, shape, mesh, fsdp=fsdp,
                           microbatches=microbatches)
    lowered = S.lower_program(prog, mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # jax<0.5 returned a one-element list
        cost = cost[0]
    hlo = compiled.as_text()
    analysis = H.analyze(hlo)   # loop-trip-aware FLOPs/bytes/collectives
    coll = analysis["collectives"]
    if PRINT_BUFFERS:
        for nbytes, desc in H.largest_shapes(hlo):
            print(f"  buf {nbytes/2**20:10.1f} MiB  {desc[:120]}")
    rec = {
        "name": name,
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "n_devices": int(np_prod(mesh.devices.shape)),
        "meta": prog.meta,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost": {
            "flops_per_device": analysis["dot_flops"],
            "bytes_per_device": analysis["hbm_bytes"],
            # raw cost_analysis for reference (counts loop bodies ONCE —
            # see hlo_analysis module docstring)
            "attn_tile_bytes": analysis["attn_tile_bytes"],
            "xla_flops_once": cost.get("flops", 0.0),
            "xla_bytes_once": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        "collectives": coll,
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def np_prod(t):
    out = 1
    for x in t:
        out *= int(x)
    return out


def fmt_row(r: dict) -> str:
    mem_gb = (r["memory"]["argument_bytes"] + r["memory"]["temp_bytes"]) / 2**30
    coll_mb = r["collectives"]["total_bytes"] / 2**20
    return (
        f"{r['arch']:<26} {r['shape']:<12} {r['mesh']:<11} "
        f"{r['cost']['flops_per_device']/1e12:>9.3f}TF "
        f"{r['cost']['bytes_per_device']/2**30:>8.2f}GiB "
        f"{coll_mb:>10.1f}MiB-coll {mem_gb:>7.2f}GiB-dev "
        f"c={r['compile_s']:>6.1f}s"
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--buffers", action="store_true",
                    help="print the largest HLO buffers (memory diagnosis)")
    ap.add_argument("--microbatches", type=int, default=0)
    args = ap.parse_args()
    global PRINT_BUFFERS
    PRINT_BUFFERS = args.buffers

    # explicit --arch/--shape always narrow the sweep; --all covers the rest
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_one(arch, shape, mp, args.out,
                                  fsdp=not args.no_fsdp, tag=args.tag,
                                  microbatches=args.microbatches)
                    print("OK  " + fmt_row(rec), flush=True)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"FAIL {arch} {shape} multi_pod={mp}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        for f in failures:
            print("  ", f)
        return 1
    print("\nall combinations lowered + compiled")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
