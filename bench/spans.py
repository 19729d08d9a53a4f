"""The program's own host spans (``repro.*``) in a traced run of a cell:
how long each host step takes, and how much device idle time falls
under it.

The benchmark's reduction keeps only the harness's ``bench.`` spans
(``trace.SPAN_PREFIX``), so no per-layer metric reads the program's spans
yet. This tool makes one traced run of a cell with the reduction keeping
``repro.`` spans too, and prints one JSON line: per call of the cell's
loop (a sweep, a structure), each span's time and the device idle time
under it. It needs the chip, as ``bench/run.py`` does::

    python3 -m bench.spans --workload fig3-sweep --seed 7 --seconds 51

``--dump <dir>`` also writes the first 2 s of the reduced trace, with the
program's spans in it (how ``tests/bench/data/*.spans.trace.json`` were
recorded).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from unittest import mock

from bench import harness, trace

#: span prefixes the reduction keeps here: the harness's and the program's
KEEP = ("bench.", "repro.")

#: per call of the cell's loop: the host steps each is read from
READINGS = {
    "draw_ms": ("span", r"^repro\.sweep\.draw$"),
    "driver_idle_ms": ("idle", r"^repro\."),
    "weights_idle_ms": ("idle", r"^repro\.structure\.(encode|gram|weights)$"),
    "edges_idle_ms": ("idle", r"^repro\.structure\.(mst|fetch|edges)$"),
}


def _in_window(tr: dict, rx) -> list:
    lo, hi = tr["window"]
    return [(s, d) for name, s, d in tr["spans"]
            if rx.search(name) and s < hi and s + d > lo]


def span_ns(tr: dict, pattern: str) -> float | None:
    """Summed in-window duration of the spans whose name matches
    ``pattern``; None where the window holds none."""
    lo, hi = tr["window"]
    hits = _in_window(tr, re.compile(pattern))
    if not hits:
        return None
    return float(sum(min(s + d, hi) - max(s, lo) for s, d in hits))


def idle_by_label(tr: dict) -> dict[str, float]:
    """``trace.idle_gaps``' attribution of the window's device idle time
    to the innermost open span ("no span" where none was), for every chip
    and averaged over the chips traced, in ns."""
    top = len(tr["spans"]) + 1
    out: dict[str, float] = {}
    for dev in tr["devices"]:
        for label, sec in trace.idle_gaps(dict(tr, devices=[dev]), top=top):
            out[label] = out.get(label, 0.0) + sec * 1e9 / len(tr["devices"])
    return out


def idle_under_ns(tr: dict, pattern: str) -> float | None:
    """Device idle time in the window whose innermost open span matches
    ``pattern``, averaged over the chips traced; None where the window
    holds no matching span or no chip."""
    rx = re.compile(pattern)
    if not _in_window(tr, rx) or not tr["devices"]:
        return None
    return sum(ns for label, ns in idle_by_label(tr).items()
               if rx.search(label))


def readings(tr: dict, calls: int) -> dict:
    """``READINGS`` in ms per call of the loop; those with nothing to read
    are left out."""
    out = {}
    for name, (kind, pattern) in READINGS.items():
        ns = (span_ns if kind == "span" else idle_under_ns)(tr, pattern)
        if ns is not None and calls:
            out[name] = ns / calls / 1e6
    return out


def per_call(tr: dict, calls: int) -> dict:
    """Each span name's count, time and device idle under it, per call."""
    lo, hi = tr["window"]
    idle = idle_by_label(tr)
    out = {}
    for name in sorted({n for n, s, d in tr["spans"] if s < hi and s + d > lo}):
        rx = "^" + re.escape(name) + "$"
        out[name] = {"count": len(_in_window(tr, re.compile(rx))) / calls,
                     "ms": span_ns(tr, rx) / calls / 1e6,
                     "idle_ms": idle.get(name, 0.0) / calls / 1e6}
    out["no span"] = {"idle_ms": idle.get("no span", 0.0) / calls / 1e6}
    return out


def traced(workload: str, seed: int, seconds: float, **kw) -> tuple[dict, dict]:
    """One traced run of the cell (``harness.run`` with ``trace=True``);
    returns its result and its whole reduced trace, ``repro.`` spans kept."""
    kept = []

    def collect(trace_dir, chips, _collect=trace.collect):
        kept.append(_collect(trace_dir, chips))
        return kept[-1]

    with mock.patch.object(trace, "SPAN_PREFIX", KEEP), \
            mock.patch.object(trace, "collect", collect):
        result = harness.run(workload, seed, seconds, True, **kw)
    return result, kept[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    try:
        result, tr = traced(args.workload, args.seed, args.seconds,
                            dump=args.dump)
    except (harness.Refused, FileNotFoundError) as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 1
    calls = result["attempted"]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "calls": calls,
        "correct": result["correct"], "device": result["device"],
        "call_ms": trace.window_ns(tr) / calls / 1e6,
        "idle_ms": (trace.window_ns(tr) - trace.busy_ns(tr)) / calls / 1e6,
        "readings": readings(tr, calls), "spans": per_call(tr, calls),
        "metrics": result["metrics"], "breakdown": result["breakdown"]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    sys.exit(main())
