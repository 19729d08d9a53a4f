"""One run of one cell: set-up, the measured window, the check, one line.

The harness knows no cell. ``BENCHMARK.json`` names the cell's
configuration and traffic; the configuration file (``configs/<name>.json``)
holds the deployment's sizes; the traffic file (``traffic/<name>.json``)
names the window loop (``entries/<entry>.py``) and its parameters, and
the limits of the compared numbers; each per-layer metric is read by
``metrics/<metric>.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: the program's own choices are what is measured
FORBIDDEN_ENV = ("REPRO_GRAM_BACKEND", "REPRO_GRAM_AUTOTUNE")
#: JAX events that mean a program was lowered or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(Exception):
    """The run cannot measure: no result is printed, the exit code is 1."""


def process_start() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, workload entry, configuration, traffic) of a cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", wl["traffic"] + ".json")
    return bench, wl, cfg, traffic


def entry_class(traffic: dict):
    path = os.path.join(HERE, "entries", traffic["entry"] + ".py")
    return load_module(path, "bench_entry_" + traffic["entry"]).Cell


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def setup_jax(chips: int, require_chip: bool):
    import jax

    if require_chip:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        # every program goes to the persistent cache, so that only a
        # cell's first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise Refused(f"no TPU: jax.devices()[0] is {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts programs lowered or compiled while ``on``."""

    _listening = None

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.on = False
        if CompileCounter._listening is None:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._hear)
        CompileCounter._listening = self

    @staticmethod
    def _hear(event, duration, **kw):
        self = CompileCounter._listening
        if self is not None and self.on and event in COMPILE_EVENTS:
            self.count += 1


class Spans:
    """Host spans around the harness's calls into the program, written
    into the profiler's trace when the run is traced."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, overrides: dict | None = None,
        dump: str | None = None) -> dict:
    """Run one cell once; return the result object (not printed).

    ``overrides`` replaces configuration and traffic keys (small sizes for
    the tests); ``require_chip=False`` lets the tests drive a run on the
    CPU. Neither is used by the benchmark's own runs."""
    t_start = process_start()
    for var in FORBIDDEN_ENV:
        if os.environ.get(var):
            raise Refused(f"{var} is set: the benchmark measures the "
                          "program's own choices")
    bench, wl, cfg, traffic = cell_spec(workload)
    for k, v in (overrides or {}).items():
        (traffic if k in traffic else cfg)[k] = v
    chips = int(wl["chips"])
    devices = setup_jax(chips, require_chip)
    from bench import roofline

    kind = devices[0].device_kind
    try:
        peak = roofline.peaks(kind)
    except KeyError as e:
        if require_chip:
            raise Refused(str(e)) from e
        peak = None
    spans = Spans(trace)
    cell = entry_class(traffic)(cfg, traffic, seed, devices, spans)
    counter = CompileCounter()
    cell.setup()
    # the set-up's objects (a pre-made trace, datasets) are the load
    # generator's, not the program's: keep the collector from scanning
    # them again and again inside the window
    gc.collect()
    gc.freeze()
    print(f"objects frozen before the window: {gc.get_freeze_count()}",
          file=sys.stderr, flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the harness's spans, not every call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.on = True
    setup_s = time.time() - t_start
    with spans("window"):
        out = cell.window(seconds)
    counter.on = False
    reduced = None
    if trace:
        import jax

        jax.profiler.stop_trace()
        from bench import trace as tracemod

        reduced = tracemod.collect(trace_dir, chips)
        if dump:
            os.makedirs(dump, exist_ok=True)
            with open(os.path.join(dump, f"{workload}.{seed}.trace.json"),
                      "w") as f:
                json.dump(tracemod.head(reduced, 2_000_000_000), f)
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"compilations inside the window: {counter.count}",
          file=sys.stderr, flush=True)
    mem = memory_peak(devices)
    gc.unfreeze()
    cell.release()
    checks = cell.check()
    correct = all(v <= lim for _, v, lim in checks)

    metrics = {}
    if trace:
        from bench import trace as tracemod

        ctx = {"trace": reduced, "counters": out["counters"], "config": cfg,
               "traffic": traffic, "peak": peak, "chips": chips}
        for m in cell_metrics(bench, workload, "per_layer"):
            reader = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, workload, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" else out["metrics"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device,
              "setup_s": setup_s}
    if trace:
        from bench import trace as tracemod

        device["busy_s"] = tracemod.busy_ns(reduced) / 1e9
        device["window_s"] = tracemod.window_ns(reduced) / 1e9
        result["breakdown"] = tracemod.breakdown(reduced)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="directory for the first 2 s of a traced run's "
                         "reduced trace (how tests/bench/data was recorded)")
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     dump=args.dump)
    except (Refused, FileNotFoundError) as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"setup_s: {result['setup_s']}", file=sys.stderr, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
