"""Serving plane: multi-tenant ingest throughput + crash-recovery gates.

Two phases:

* **throughput** — a 64-tenant server (4 machines each, sign payloads,
  light wire pathologies) ingests a deterministic trace tick by tick;
  reports sustained ticks/s, payload-fold rows/s, and the per-tick fold
  latency distribution (p50/p99) with snapshots riding every few ticks.
* **crash recovery** — the acceptance gate. A child process runs the
  same trace but SIGKILLs itself mid-tick (between the journal append
  and the fold — the worst WAL window); the parent restores from the
  snapshot + journal on disk, re-delivers everything unacked, and
  compares accumulators / counts / cursors / structures against an
  uninterrupted run BIT FOR BIT, with duplicated + reordered + dropped
  deliveries in the trace. Also reports snapshot-restore + journal
  replay wall time.

Checks: ``crash_restore_bit_identical`` (the hard gate),
``folds_exactly_once`` (server accumulators equal an independent
exactly-once reference fold), ``drained_clean`` (no payload stuck in
reorder buffers at the end).
Artifact: ``BENCH_serve.json`` via ``benchmarks.run --only serve --json``.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core.streaming import StreamingGram
from repro.serve import (ServeConfig, StructureServer, TrafficConfig,
                         make_trace, unique_payloads)

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")

_CHILD = """\
import sys
from repro.serve import ServeConfig, StructureServer, TrafficConfig, \\
    make_trace

tcfg = TrafficConfig(**{tcfg!r})
scfg = ServeConfig(**{scfg!r}, crash_after_journal_records={crash})
srv = StructureServer(scfg, sys.argv[1])
for batch in make_trace(tcfg):
    for p in batch:
        srv.submit(p)
    srv.run_tick()
print("SURVIVED")  # must be unreachable: the hook SIGKILLs mid-trace
sys.exit(3)
"""


def _drive(srv: StructureServer, trace, extra_ticks: int = 6):
    stats = []
    for batch in trace:
        for p in batch:
            srv.submit(p)
        stats.append(srv.run_tick())
    for _ in range(extra_ticks):
        stats.append(srv.run_tick())
    srv.force_resolve()
    return stats


def _reference_match(srv: StructureServer, trace, d: int) -> bool:
    """Accumulators equal an independent exactly-once fold (sign path:
    exact integers, so any fold order matches bit for bit)."""
    refs: dict[int, StreamingGram] = {}
    import jax.numpy as jnp

    for p in unique_payloads(trace):
        sg = refs.setdefault(p.tenant, StreamingGram(d=d, method="sign"))
        if p.kind == "codes":
            sg.update_codes(jnp.asarray(p.codes))
        else:
            sg.update_packed(jnp.asarray(p.packed), p.n)
    return all(
        np.array_equal(np.asarray(sg.gram, np.float64), srv.table.gram[t])
        and sg.n == int(srv.table.n[t]) for t, sg in refs.items())


def _throughput_phase(quick: bool, workdir: str) -> dict:
    tenants = 16 if quick else 64
    tcfg = dict(tenants=tenants, machines=4, ticks=6 if quick else 20,
                n=48, d=16 if quick else 32, p_duplicate=0.05,
                p_reorder=0.05, p_drop=0.02, seed=3)
    scfg = dict(tenants=tenants, machines=4, d=tcfg["d"], block_n=48,
                snapshot_every=4, reorder_ticks=2,
                fold_budget=tenants * 8, queue_capacity=tenants * 16)
    trace = make_trace(TrafficConfig(**tcfg))
    srv = StructureServer(ServeConfig(**scfg), os.path.join(workdir, "tp"))
    t0 = time.perf_counter()
    stats = _drive(srv, trace)
    wall = time.perf_counter() - t0
    folds = sorted(s["fold_seconds"] for s in stats)
    rows = sum(s["rows"] for s in stats)
    last = stats[-1]
    out = {
        "tenants": tenants, "machines": 4, "d": tcfg["d"],
        "block_n": 48, "ticks": len(stats),
        "ticks_per_s": len(stats) / wall,
        "rows_per_s": rows / wall,
        "fold_p50_ms": 1e3 * folds[len(folds) // 2],
        "fold_p99_ms": 1e3 * folds[int(len(folds) * 0.99)],
        "telemetry": {k: last[k] for k in (
            "duplicates", "reordered", "lost", "degraded_tenants",
            "watchdog_fires", "rejected")},
        "drained_clean": srv.log.buffered() == 0,
        "folds_exactly_once": _reference_match(srv, trace, tcfg["d"]),
    }
    srv.close()
    return out


def run(quick: bool = False) -> dict:
    workdir = tempfile.mkdtemp(prefix="bench_serve_")
    try:
        tp = _throughput_phase(quick, workdir)
        cr = _crash(quick, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    payload = {
        **tp,
        "recovery": {k: cr[k] for k in (
            "crash_after_records", "recovered_records",
            "recovery_seconds", "snapshot_step", "torn_segments",
            "torn_bytes_dropped")},
        "checks": {
            "crash_restore_bit_identical": cr["bit_identical"],
            "folds_exactly_once": tp["folds_exactly_once"],
            "drained_clean": tp["drained_clean"],
        },
    }
    print(f"serve: {tp['tenants']} tenants  {tp['ticks_per_s']:.1f} ticks/s"
          f"  {tp['rows_per_s']:.0f} rows/s  fold p50 "
          f"{tp['fold_p50_ms']:.1f}ms p99 {tp['fold_p99_ms']:.1f}ms")
    print(f"serve: crash@{cr['crash_after_records']} records -> replayed "
          f"{cr['recovered_records']} in {cr['recovery_seconds']*1e3:.0f}ms"
          f", bit_identical={cr['bit_identical']}")
    return payload


def _crash(quick: bool, workdir: str) -> dict:
    tcfg = dict(tenants=8, machines=3, ticks=8 if quick else 12, n=24,
                d=12, p_duplicate=0.25, p_reorder=0.25, p_drop=0.1, seed=11)
    scfg = dict(tenants=8, machines=3, d=12, block_n=24,
                snapshot_every=3, reorder_ticks=2)
    trace = make_trace(TrafficConfig(**tcfg))
    clean = StructureServer(
        ServeConfig(**scfg), os.path.join(workdir, "clean"))
    _drive(clean, trace)

    crash_dir = os.path.join(workdir, "crash")
    crash_after = 30 if quick else 60
    # The child is an explicit CPU process: this process may hold the
    # chip, and a second process cannot open it. Bit-identity still holds
    # across the two platforms: the child only journals and folds sign
    # payloads, whose Grams are exact integers on every backend and sum in
    # float64 on the host, and the final force_resolve re-solves every
    # tenant here, so the compared structures come from one device.
    env = dict(os.environ, PYTHONPATH=_SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c",
         _CHILD.format(tcfg=tcfg, scfg=scfg, crash=crash_after), crash_dir],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == -9, (
        f"crash child exited {r.returncode} instead of SIGKILL:\n"
        f"{r.stdout}\n{r.stderr}")

    srv = StructureServer(ServeConfig(**scfg), crash_dir)  # replays the WAL
    recovered = {"records": srv.recovered_records,
                 "seconds": srv.recovery_seconds,
                 "step": srv.snapshot_step,
                 "torn_segments": srv.torn_segments,
                 "torn_bytes_dropped": srv.torn_bytes_dropped}
    _drive(srv, trace)            # producers re-send everything unacked
    a, b = clean.comparable_state(), srv.comparable_state()
    bit_identical = all(np.array_equal(a[k], b[k]) for k in a)
    clean.close()
    srv.close()
    return {
        "crash_after_records": crash_after,
        "recovered_records": recovered["records"],
        "recovery_seconds": recovered["seconds"],
        "snapshot_step": recovered["step"],
        "torn_segments": recovered["torn_segments"],
        "torn_bytes_dropped": recovered["torn_bytes_dropped"],
        "bit_identical": bit_identical,
    }


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)
