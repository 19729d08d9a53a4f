"""Benchmark driver: one module per paper table/figure + framework tables.

  PYTHONPATH=src python -m benchmarks.run            # full (slow)
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
  PYTHONPATH=src python -m benchmarks.run --only fig3,roofline
  PYTHONPATH=src python -m benchmarks.run --only gram --json   # BENCH_gram.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import (bigd, channels, ext_glasso, faults, fig3_structure_error,
               fig56_crossover, fig7_star, fig8_rel_error,
               fig9_quality_quantity, fig1011_skeleton, ggm_comm,
               ggm_roofline, gram_engine, kernel_throughput, path,
               roofline, serve, sparse, trials)

BENCHES = {
    "bigd": bigd.run,
    "channels": channels.run,
    "fig3": fig3_structure_error.run,
    "fig56": fig56_crossover.run,
    "fig7": fig7_star.run,
    "fig8": fig8_rel_error.run,
    "fig9": fig9_quality_quantity.run,
    "fig1011": fig1011_skeleton.run,
    "ggm_comm": ggm_comm.run,
    "ggm_roofline": ggm_roofline.run,
    "ext_glasso": ext_glasso.run,
    "faults": faults.run,
    "gram": gram_engine.run,
    "kernels": kernel_throughput.run,
    "path": path.run,
    "roofline": roofline.run,
    "serve": serve.run,
    "sparse": sparse.run,
    "trials": trials.run,
}

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_GRAM_JSON = os.path.join(_REPO_ROOT, "BENCH_gram.json")
BENCH_TRIALS_JSON = os.path.join(_REPO_ROOT, "BENCH_trials.json")
BENCH_SPARSE_JSON = os.path.join(_REPO_ROOT, "BENCH_sparse.json")
BENCH_FAULTS_JSON = os.path.join(_REPO_ROOT, "BENCH_faults.json")
BENCH_BIGD_JSON = os.path.join(_REPO_ROOT, "BENCH_bigd.json")
BENCH_ROOFLINE_JSON = os.path.join(_REPO_ROOT, "BENCH_roofline.json")
BENCH_SERVE_JSON = os.path.join(_REPO_ROOT, "BENCH_serve.json")
BENCH_PATH_JSON = os.path.join(_REPO_ROOT, "BENCH_path.json")
BENCH_CHANNELS_JSON = os.path.join(_REPO_ROOT, "BENCH_channels.json")


def _write_slim(payload: dict, keys: tuple, path: str) -> str:
    """Shared slim-artifact writer (the gram artifact needs bespoke row
    slicing and keeps its own)."""
    with open(path, "w") as f:
        json.dump({k: payload[k] for k in keys}, f, indent=1, default=float)
    return path


def write_bench_sparse(payload: dict, path: str = BENCH_SPARSE_JSON) -> str:
    """Persist the sparse-trial-plane artifact: per-(strategy, n) support
    recovery (F1/precision/recall) + comm accounting, engine throughput,
    and the parity / one-sync acceptance checks."""
    return _write_slim(payload, (
        "d", "lam", "density", "ns", "reps", "strategies", "glasso_tol",
        "glasso_steps", "engine", "wire_parity", "rows", "path",
        "checks"), path)


def write_bench_faults(payload: dict, path: str = BENCH_FAULTS_JSON) -> str:
    """Persist the fault-plane artifact: per-scenario structure error +
    realized fault telemetry + measured retry accounting, and the
    zero-fault-identity / one-sync / degradation-gate checks."""
    return _write_slim(payload, (
        "d", "machines", "ns", "reps", "strategies", "degradation_margin",
        "scenarios", "rows", "checks"), path)


def write_bench_trials(payload: dict, path: str = BENCH_TRIALS_JSON) -> str:
    """Persist the trial-plane perf artifact: sweep-engine trials/s per
    mode (exact / bucketed / sharded, cold and warm) vs the legacy
    per-trial loop, and the speedups + acceptance checks."""
    return _write_slim(payload, (
        "backend", "d", "ns", "reps", "strategies", "trials", "buckets",
        "engine", "loop", "speedup_warm", "speedup_cold", "cold_vs_pr2",
        "comm", "checks"), path)


def write_bench_bigd(payload: dict, path: str = BENCH_BIGD_JSON) -> str:
    """Persist the large-d engine artifact: tiled-vs-monolithic timing per
    Gram path, autotuned-vs-default-tile speedups, the d=4096 memory-budget
    contrast, and the bit-identity / budget / speedup acceptance checks."""
    return _write_slim(payload, (
        "backend", "n", "ds", "rows", "autotune", "budget",
        "bytes_ratio_f32_over_packed", "checks"), path)


def write_bench_roofline(payload: dict, path: str = BENCH_ROOFLINE_JSON) -> str:
    """Persist the distributed-GGM roofline artifact: per-(placement, shape)
    analytic collective/compute/HBM bounds from the AOT-lowered program,
    the binding term, and the model-sanity checks (see ggm_roofline.py)."""
    return _write_slim(payload, (
        "platform", "d", "n", "rows", "checks"), path)


def write_bench_serve(payload: dict, path: str = BENCH_SERVE_JSON) -> str:
    """Persist the serving-plane artifact: multi-tenant ingest throughput
    (ticks/s, rows/s, fold latency p50/p99), wire-pathology telemetry,
    snapshot+journal recovery timing, and the crash-restore bit-identity /
    exactly-once acceptance checks."""
    return _write_slim(payload, (
        "tenants", "machines", "d", "block_n", "ticks", "ticks_per_s",
        "rows_per_s", "fold_p50_ms", "fold_p99_ms", "telemetry",
        "recovery", "checks"), path)


def write_bench_path(payload: dict, path: str = BENCH_PATH_JSON) -> str:
    """Persist the regularization-path artifact: fused-vs-per-lam sweep
    timing, selected-support quality, per-lam early-exit iteration
    telemetry, and the speedup / one-sync / oracle-selection checks."""
    return _write_slim(payload, (
        "d", "n", "batch", "lams", "n_steps", "conv_tol",
        "baseline_seconds", "fused_seconds", "speedup", "host_syncs",
        "f1_fused", "f1_baseline", "iters_total_fused",
        "iters_total_baseline", "rows", "checks"), path)


def write_bench_channels(payload: dict,
                         path: str = BENCH_CHANNELS_JSON) -> str:
    """Persist the channel-plane artifact: per-(strategy, n) structure
    error + per-machine bit ledgers for the gather / MAC-superposition /
    budget wires, and the gather-bit-identity / one-sync / budget-bound
    acceptance checks."""
    return _write_slim(payload, (
        "d", "machines", "ns", "reps", "budget_bits", "cap", "strategies",
        "scenarios", "rows", "checks"), path)


def write_bench_gram(payload: dict, path: str = BENCH_GRAM_JSON) -> str:
    """Persist the perf-trajectory artifact tracked across PRs: per-backend
    GB/s and GFLOP/s for every Gram path, plus the bytes-moved check."""
    slim = {
        "rows": [
            {k: r[k] for k in ("path", "backend", "n", "d", "bytes_moved",
                               "gbps", "gflops_per_s", "seconds")}
            for r in payload["rows"]
        ],
        "acceptance": payload["acceptance"],
        "checks": payload["checks"],
    }
    with open(path, "w") as f:
        json.dump(slim, f, indent=1, default=float)
    return path


def use_compile_cache() -> None:
    """Keep XLA's persistent compile cache at one fixed path in the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` already names one (the
    path is part of the cache key, so it must not move between runs)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO_ROOT, ".jax_cache"))


def main() -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_gram.json / BENCH_trials.json (runs "
                         "the gram and trials benches if not selected)")
    args = ap.parse_args()
    names = [n for n in args.only.split(",") if n] or list(BENCHES)
    if args.json:
        names.extend(n for n in ("gram", "trials") if n not in names)

    failures = []
    for name in names:
        print(f"\n=== {name} " + "=" * (68 - len(name)), flush=True)
        t0 = time.time()
        try:
            result = BENCHES[name](quick=args.quick)
            if name == "gram" and args.json:
                print("wrote", write_bench_gram(result), flush=True)
            if name == "trials" and args.json:
                print("wrote", write_bench_trials(result), flush=True)
            if name == "sparse" and args.json:
                print("wrote", write_bench_sparse(result), flush=True)
            if name == "faults" and args.json:
                print("wrote", write_bench_faults(result), flush=True)
            if name == "bigd" and args.json:
                print("wrote", write_bench_bigd(result), flush=True)
            if name == "ggm_roofline" and args.json:
                print("wrote", write_bench_roofline(result), flush=True)
            if name == "serve" and args.json:
                print("wrote", write_bench_serve(result), flush=True)
            if name == "path" and args.json:
                print("wrote", write_bench_path(result), flush=True)
            if name == "channels" and args.json:
                print("wrote", write_bench_channels(result), flush=True)
            checks = (result or {}).get("checks", {})
            bad = [k for k, v in checks.items() if not v]
            status = "PASS" if not bad else f"CHECKS-FAILED:{bad}"
            if bad:
                failures.append((name, bad))
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            status = f"ERROR: {e}"
        print(f"=== {name} [{status}] ({time.time()-t0:.1f}s)", flush=True)

    print("\n" + "=" * 72)
    if failures:
        print(f"{len(failures)} benchmark(s) with failed checks/errors:")
        for f in failures:
            print("  ", f)
        return 1
    print("all benchmarks passed their paper-claim checks")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
