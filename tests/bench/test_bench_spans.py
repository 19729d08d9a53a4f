"""The program's host spans (``repro.*``) and what ``bench/spans.py`` reads
from them: on a hand-made reduced trace, in a traced CPU run of each cell
at a small size, and on recorded chip traces that carry them."""
import collections
import glob
import json
import os

import pytest

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _nested_trace():
    # window [0, 200), one chip busy over [45, 88) and [118, 168)
    sweep = [["bench.run_trials", 0, 100], ["repro.run_trials", 2, 96],
             ["repro.sweep.draw", 4, 36], ["repro.sweep.point", 40, 10],
             ["repro.sweep.point", 50, 10], ["repro.sweep.sync", 60, 30],
             ["repro.sweep.report", 90, 7]]
    structure = [["bench.learn_structure", 110, 80],
                 ["repro.learn_structure", 111, 78],
                 ["repro.structure.encode", 112, 8],
                 ["repro.structure.gram", 120, 5],
                 ["repro.structure.weights", 125, 5],
                 ["repro.structure.mst", 130, 5],
                 ["repro.structure.fetch", 135, 35],
                 ["repro.structure.edges", 170, 15]]
    ops = [["jit_f", 45, 43, "jit_f"], ["sign_corr_packed", 118, 50, "m"]]
    return {"window": [0, 200],
            "devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
            "spans": sorted(sweep + structure)}


#: ns of idle under each innermost span of ``_nested_trace``: gaps
#: [0, 45), [88, 118) and [168, 200)
NESTED_IDLE = {"bench.run_trials": 4, "repro.run_trials": 3,
               "repro.sweep.draw": 36, "repro.sweep.point": 5,
               "repro.sweep.sync": 2, "repro.sweep.report": 7,
               "bench.learn_structure": 2, "repro.learn_structure": 5,
               "repro.structure.encode": 6, "repro.structure.fetch": 2,
               "repro.structure.edges": 15, "no span": 20}


def test_span_and_idle_readings_on_a_hand_made_trace():
    tr = _nested_trace()
    assert spans.span_ns(tr, r"^repro\.sweep\.draw$") == 36
    assert spans.span_ns(tr, r"^repro\.sweep\.point$") == 20
    assert spans.span_ns(tr, r"^repro\.nothing$") is None
    assert spans.idle_by_label(tr) == pytest.approx(NESTED_IDLE)
    assert spans.idle_under_ns(tr, r"^repro\.") == pytest.approx(81)
    assert spans.idle_under_ns(tr, r"^repro\.structure\.mst$") == 0
    assert spans.readings(tr, 1) == pytest.approx({
        "draw_ms": 36e-6, "driver_idle_ms": 81e-6,
        "weights_idle_ms": 6e-6, "edges_idle_ms": 17e-6})
    assert spans.per_call(tr, 1)["repro.sweep.point"] == pytest.approx(
        {"count": 2, "ms": 20e-6, "idle_ms": 5e-6})


def test_nested_program_spans_take_the_harness_spans_idle():
    tr = _nested_trace()
    idle = dict(trace.idle_gaps(tr, top=100))
    assert idle == pytest.approx({k: v * 1e-9 for k, v in NESTED_IDLE.items()})
    bare = dict(tr, spans=[sp for sp in tr["spans"]
                           if sp[0].startswith("bench.")])
    before = dict(trace.idle_gaps(bare, top=100))
    for root, steps in (("bench.run_trials", "repro.run_trials repro.sweep."),
                        ("bench.learn_structure",
                         "repro.learn_structure repro.structure.")):
        moved = sum(v for k, v in idle.items()
                    if k.startswith(tuple(steps.split())))
        assert before[root] == pytest.approx(idle[root] + moved)
    assert sum(before.values()) == pytest.approx(sum(idle.values()))
    assert sum(idle.values()) * 1e9 == pytest.approx(
        trace.window_ns(tr) - trace.busy_ns(tr))


def test_idle_under_spans_averages_over_chips():
    tr = _nested_trace()
    tr["devices"].append({"name": "/device:TPU:1",
                          "ops": [["fusion", 0, 200, "m"]], "modules": []})
    assert spans.idle_under_ns(tr, r"^repro\.") == pytest.approx(81 / 2)


def test_nothing_to_read_without_program_spans():
    tr = _nested_trace()
    bare = dict(tr, spans=[sp for sp in tr["spans"]
                           if sp[0].startswith("bench.")])
    assert spans.readings(bare, 1) == {}
    assert spans.idle_under_ns(dict(tr, devices=[]), r"^repro\.") is None


SMALL = {"fig3-sweep": {"reps": 16, "ns": [500, 2000]},
         "d1024-structure": {"d": 32, "n": 512, "datasets": 2}}
#: the harness's span around each call, and the program's spans per call
PER_CALL = {
    "fig3-sweep": ("bench.run_trials", {
        "repro.run_trials": 1, "repro.sweep.draw": 1,
        "repro.sweep.point": len(SMALL["fig3-sweep"]["ns"]),
        "repro.sweep.sync": 1, "repro.sweep.report": 1}),
    "d1024-structure": ("bench.learn_structure", {
        "repro.learn_structure": 1, "repro.structure.encode": 1,
        "repro.structure.gram": 1, "repro.structure.weights": 1,
        "repro.structure.mst": 1, "repro.structure.fetch": 1,
        "repro.structure.edges": 1}),
}


@pytest.mark.parametrize("workload", sorted(PER_CALL))
def test_program_spans_in_a_traced_cpu_run(workload):
    result, tr = spans.traced(workload, 2**31 + 13, 0.5, require_chip=False,
                              overrides=SMALL[workload])
    assert result["correct"], result["checks"]
    calls = result["attempted"]
    assert calls >= 1
    root, per_call = PER_CALL[workload]
    lo, hi = tr["window"]
    roots = [(s, s + d) for name, s, d in tr["spans"] if name == root]
    assert len(roots) == calls
    for name, s, d in tr["spans"]:
        assert lo <= s and s + d <= hi, name
        if name.startswith("repro."):
            assert any(a <= s and s + d <= b for a, b in roots), name
    counts = collections.Counter(name for name, _, _ in tr["spans"])
    want = {name: k * calls for name, k in per_call.items()}
    assert counts == collections.Counter({root: calls, **want})
    got = spans.per_call(tr, calls)
    assert {k: v["count"] for k, v in got.items() if k in per_call} == per_call


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(DATA, "*.spans.trace.json"))))
def test_recorded_chip_trace_puts_the_idle_under_program_steps(path):
    with open(path) as f:
        tr = json.load(f)
    idle = trace.window_ns(tr) - trace.busy_ns(tr)
    labels = spans.idle_by_label(tr)
    assert sum(labels.values()) == pytest.approx(idle, rel=1e-6)
    program = spans.idle_under_ns(tr, r"^repro\.")
    harness = sum(v for k, v in labels.items()
                  if k in ("bench.run_trials", "bench.learn_structure"))
    assert program >= 0.9 * (program + harness)
