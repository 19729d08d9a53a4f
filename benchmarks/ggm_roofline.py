"""Roofline + perf ladder for the paper's own distributed pipeline.

Production GGM config: d = 4096 features over the 16-way model axis
(256 paper-machines per device), n = 2^20 samples over the 16-way data
axis. For each (wire format x compute placement) the program is
AOT-lowered on the production mesh and the collective/compute terms are
derived exactly like the LM dry-run.

The ladder IS the §Perf story for the paper's technique:
  float32 wire, replicated Gram   — centralized-equivalent baseline
  int8 codes, replicated          — paper-faithful (sign/per-symbol), lazy wire
  packed R-bit, replicated        — paper's true budget (1 bit/symbol sign)
  packed R-bit, rowblock Gram     — beyond-paper: also fix the compute term

Each row also carries the roofline schema the acceptance plumbing reads:
``bound_ms`` (the binding analytic term) and ``limiter`` (which term
binds). The mesh is 512 *forced* host devices — no 1- or 4-chip host has
that many — so the ladder is analytic only: nothing here is a time.

Run in its own process (needs the 512-device flag BEFORE jax init):
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.ggm_roofline
"""
from __future__ import annotations

import os
import sys


def run(quick: bool = False) -> dict:
    # this benchmark needs 512 host devices: always run it in a child
    # that is explicitly a CPU process, so it never asks for a chip that
    # the calling process (benchmarks.run) may hold
    import json
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.ggm_roofline",
         *(['--quick'] if quick else [])],
        capture_output=True, text=True, timeout=4000, env=env,
    )
    print(out.stdout, end="")
    if out.returncode != 0:
        print(out.stderr[-2000:])
        return {"checks": {"subprocess_ok": False}}
    art = os.path.join(os.path.dirname(__file__), "artifacts",
                       "ggm_roofline.json")
    with open(art) as f:
        return json.load(f)


def _run_inprocess(quick: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import build_weights_fn, communication_bits
    from repro.launch import hlo_analysis as H
    from repro.launch.mesh import make_production_mesh
    from .common import save_artifact
    from .roofline import HBM_BW, ICI_BW, PEAK_FLOPS

    d, n = (1024, 1 << 16) if quick else (4096, 1 << 20)
    mesh = make_production_mesh()
    x_spec = jax.ShapeDtypeStruct((n, d), jnp.float32)

    ladder = [
        ("float32-replicated", dict(method="sign", wire="float32",
                                    compute="replicated")),
        ("sign-int8-replicated", dict(method="sign", wire="int8",
                                      compute="replicated")),
        ("sign-packed-replicated", dict(method="sign", wire="packed",
                                        compute="replicated")),
        ("sign-packed-rowblock", dict(method="sign", wire="packed",
                                      compute="rowblock")),
        ("ps4-packed-rowblock", dict(method="persymbol", rate=4,
                                     wire="packed", compute="rowblock")),
    ]
    rows = []
    with mesh:
        for name, kw in ladder:
            fn, sharding = build_weights_fn(mesh, **kw)
            lowered = jax.jit(fn, in_shardings=(sharding,)).lower(x_spec)
            compiled = lowered.compile()
            a = H.analyze(compiled.as_text())
            coll = a["collectives"]["total_bytes"]
            flops = a["dot_flops"]
            terms = {
                "collective_ms": coll / ICI_BW * 1e3,
                "compute_ms": flops / PEAK_FLOPS * 1e3,
                "hbm_ms": a["hbm_bytes"] / HBM_BW * 1e3,
            }
            limiter = max(terms, key=terms.get)
            rows.append({
                "variant": name,
                "collective_bytes": coll,
                "by_op": a["collectives"]["by_op"],
                "wire_bytes": a["collectives"]["by_op"].get("all-gather", 0.0),
                "dot_flops": flops,
                **terms,
                "bound_ms": terms[limiter],
                "limiter": limiter,
                "paper_wire_bits": communication_bits(
                    n, d, {"float32": 32}.get(kw["wire"], kw.get("rate", 1))),
            })
            r = rows[-1]
            print(f"ggm {name:<24} coll={coll/2**20:9.1f}MiB "
                  f"({r['collective_ms']:7.2f}ms) "
                  f"compute={r['compute_ms']:7.2f}ms "
                  f"hbm={r['hbm_ms']:7.2f}ms "
                  f"bound={limiter.removesuffix('_ms')}", flush=True)

    by = {r["variant"]: r for r in rows}
    checks = {
        # the WIRE (code all-gather) is the paper's metric; the Gram psum
        # is a separate (fixed) term the ladder's rowblock step addresses
        "sign_int8_cuts_wire_4x": by["sign-int8-replicated"]["wire_bytes"]
        < by["float32-replicated"]["wire_bytes"] / 3.5,
        "packing_cuts_wire_8x": by["sign-packed-replicated"]["wire_bytes"]
        < by["sign-int8-replicated"]["wire_bytes"] / 6,
        "rowblock_cuts_flops": by["sign-packed-rowblock"]["dot_flops"]
        < by["sign-packed-replicated"]["dot_flops"] / 8,
        # the 8x end-to-end bound is the production-shape claim; at the
        # --quick shape the fixed all-reduce term is a larger share of the
        # (smaller) wire, so the ladder closes 4x, not 8x
        "end_to_end_bound_improves": max(
            by["sign-packed-rowblock"]["collective_ms"],
            by["sign-packed-rowblock"]["compute_ms"])
        < max(by["float32-replicated"]["collective_ms"],
              by["float32-replicated"]["compute_ms"]) / (4 if quick else 8),
    }
    payload = {
        "platform": jax.default_backend(), "d": d, "n": n, "rows": rows,
        "checks": checks,
    }
    save_artifact("ggm_roofline", payload)
    return payload


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"  # 512 forced host devices, no chip
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    _run_inprocess("--quick" in sys.argv)
