"""Device traces -> the numbers the per-layer metrics read.

``collect`` turns the profiler's ``.xplane.pb`` into a small dict (the
*reduced trace*), which is also the form a recorded trace is kept in for
the tests::

    {"window": [start_ns, end_ns],               # the harness's window span
     "devices": [{"name": "/device:TPU:0",
                  "ops": [[op, start_ns, dur_ns, module], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "spans": [[name, start_ns, dur_ns], ...]}    # the harness's host spans

Everything below works on that dict: the busy union of each chip's
operations, sums over kernels and modules, the part of a collective with
no compute beside it, and the device's idle gaps attributed to the host
span that was open while the device waited.
"""
from __future__ import annotations

import glob
import os
import re

#: device planes of the chips, not their SparseCores or the host
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans the harness writes (``jax.profiler.TraceAnnotation``)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: collective operations, by HLO op name
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute")
#: ops that contain other ops of the same line (their bodies are listed
#: on their own), left out of sums by op
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(text: str) -> str:
    """The HLO instruction's name from a TPU op event's text
    (``"%fusion.81 = s32[...] fusion(...)"`` -> ``"fusion.81"``); a Pallas
    kernel's instruction is named after its kernel (``"sign_corr_packed"``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _modules_of(ops, modules):
    """The module executing at each op's start (modules run one at a time
    on a chip); '' where none does."""
    import bisect

    starts = [s for _, s, _ in modules]
    out = []
    for _, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        ok = i >= 0 and s < modules[i][1] + modules[i][2]
        out.append(re.sub(r"\(\d+\)$", "", modules[i][0]) if ok else "")
    return out


def collect(trace_dir: str, chips: int) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if int(m.group(1)) >= chips:
                continue
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(op_name(e.name), int(e.start_ns),
                            int(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = sorted((e.name, int(e.start_ns),
                                      int(e.duration_ns)) for e in line.events)
            modules.sort(key=lambda m: m[1])
            devices.append({
                "name": plane.name,
                "ops": [[n, s, d, m] for (n, s, d), m in
                        zip(ops, _modules_of(ops, modules))],
                "modules": [list(m) for m in modules]})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    devices.sort(key=lambda dv: int(DEVICE_PLANE.match(dv["name"]).group(1)))
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if not window:
        raise RuntimeError("the trace holds no window span")
    w = max(window, key=lambda s: s[2])
    return {"window": [w[1], w[1] + w[2]], "devices": devices,
            "spans": sorted(s for s in spans if s[0] != WINDOW_SPAN)}


def head(tr: dict, ns: int) -> dict:
    """The reduced trace cut to the first ``ns`` of its window."""
    w0 = tr["window"][0]
    w1 = min(tr["window"][1], w0 + ns)
    keep = lambda s, d: s < w1 and s + d > w0  # noqa: E731
    return {"window": [w0, w1],
            "spans": [sp for sp in tr["spans"] if keep(sp[1], sp[2])],
            "devices": [{"name": dv["name"],
                         "ops": [o for o in dv["ops"] if keep(o[1], o[2])],
                         "modules": [m for m in dv["modules"] if keep(m[1], m[2])]}
                        for dv in tr["devices"]]}


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Sorted disjoint union of [start, end) intervals clipped to [lo, hi)."""
    out: list[list[int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def minus(a, b) -> int:
    """Length of union ``a`` not covered by union ``b`` (both disjoint)."""
    covered, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return length(a) - covered


def _ops(dev, lo, hi, match=None):
    return [(s, s + d) for name, s, d, _ in dev["ops"]
            if (match is None or match(name)) and s < hi and s + d > lo]


# --------------------------------------------------------------------------
# what the metrics read
# --------------------------------------------------------------------------

def window_ns(tr: dict) -> int:
    return tr["window"][1] - tr["window"][0]


def busy_ns(tr: dict) -> float:
    """Busy time inside the window, averaged over the chips traced."""
    lo, hi = tr["window"]
    per = [length(union(_ops(dev, lo, hi), lo, hi)) for dev in tr["devices"]]
    return sum(per) / len(per) if per else 0.0


def op_ns(tr: dict, pattern: str) -> float:
    """Summed device time of ops whose name matches ``pattern`` inside the
    window, averaged over the chips traced."""
    lo, hi = tr["window"]
    rx = re.compile(pattern)
    per = [sum(min(s + d, hi) - max(s, lo) for name, s, d, _ in dev["ops"]
               if rx.search(name) and s < hi and s + d > lo)
           for dev in tr["devices"]]
    return sum(per) / len(per) if per else 0.0


def module_ns(tr: dict, pattern: str) -> float:
    """Summed device time of modules whose name matches ``pattern``,
    averaged over the chips traced."""
    lo, hi = tr["window"]
    rx = re.compile(pattern)
    per = [sum(min(s + d, hi) - max(s, lo) for name, s, d in dev["modules"]
               if rx.search(name) and s < hi and s + d > lo)
           for dev in tr["devices"]]
    return sum(per) / len(per) if per else 0.0


def exposed_ns(tr: dict, pattern: str = COLLECTIVE.pattern) -> float:
    """Time of matching ops with no other op running beside them on the
    same chip, averaged over the chips traced."""
    lo, hi = tr["window"]
    rx = re.compile(pattern)
    per = []
    for dev in tr["devices"]:
        coll = union(_ops(dev, lo, hi, rx.search), lo, hi)
        other = union(_ops(dev, lo, hi, lambda n: not rx.search(n)), lo, hi)
        per.append(minus(coll, other))
    return sum(per) / len(per) if per else 0.0


def _op_key(name: str, module: str) -> str:
    op = re.sub(r"\.\d+$", "", name)
    return f"{module}/{op}" if module else op


def device_ops(tr: dict, top: int = 10) -> list[list]:
    """The ``top`` operations (module/op, numeric suffix dropped) by summed
    device time in the window, averaged over chips: [[name, seconds]].
    Loops and calls are left out: their bodies' ops are counted."""
    lo, hi = tr["window"]
    tot: dict[str, float] = {}
    for dev in tr["devices"]:
        for name, s, d, module in dev["ops"]:
            if s < hi and s + d > lo and not CONTAINER.match(name):
                k = _op_key(name, module)
                tot[k] = tot.get(k, 0.0) + (min(s + d, hi) - max(s, lo))
    n = max(len(tr["devices"]), 1)
    return [[k, v / n / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def _innermost(spans) -> list[tuple[int, int, str]]:
    """Disjoint sorted segments, each labelled with the shortest span
    open over it (the innermost, for nested spans)."""
    cuts = sorted({t for _, s, d in spans for t in (s, s + d)})
    order = sorted(spans, key=lambda sp: sp[1])
    out, active, j = [], [], 0
    for x, y in zip(cuts, cuts[1:]):
        while j < len(order) and order[j][1] <= x:
            active.append(order[j])
            j += 1
        active = [sp for sp in active if sp[1] + sp[2] > x]
        if active:
            out.append((x, y, min(active, key=lambda sp: sp[2])[0]))
    return out


def idle_gaps(tr: dict, top: int = 10) -> list[list]:
    """Idle time of the first chip in the window, attributed piece by
    piece to the innermost harness span open at the time ("no span" where
    none was), summed by span: the ``top`` labels, [[label, seconds]]."""
    lo, hi = tr["window"]
    if not tr["devices"]:
        return []
    busy = union(_ops(tr["devices"][0], lo, hi), lo, hi)
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    segs = _innermost(tr["spans"])
    tot: dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(segs) and segs[k][0] < b:
            x, y = max(a, segs[k][0]), min(b, segs[k][1])
            tot[segs[k][2]] = tot.get(segs[k][2], 0.0) + (y - x)
            covered += y - x
            k += 1
        if b - a > covered:
            tot["no span"] = tot.get("no span", 0.0) + (b - a - covered)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def breakdown(tr: dict) -> dict:
    return {"device_ops": device_ops(tr), "idle_gaps": idle_gaps(tr)}

