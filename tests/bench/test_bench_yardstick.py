"""The benchmark's yardstick: roofline counts, peaks, the trace
reduction (on a hand-made trace and on a recorded one), the shape of
BENCHMARK.json, and the harness's refusals."""
import glob
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, roofline, trace
from bench.reference import tree

ROOT = harness.ROOT
DATA = os.path.join(os.path.dirname(__file__), "data")


def test_gram_counts():
    # 2 n d_a d_b operations at the true n
    assert roofline.gram_ops(4000, 20) == 2 * 4000 * 20 * 20
    assert roofline.gram_ops(100, 8, 16) == 2 * 100 * 8 * 16
    # payload once for U^T U, plus the f32 output
    assert roofline.gram_bytes(65536, "packed", 1024) == 1024 * 8192 + 4 * 1024 ** 2
    assert roofline.gram_bytes(4000, "int8", 20) == 20 * 4000 + 4 * 400
    assert roofline.gram_bytes(4000, "float32", 20) == 20 * 16000 + 4 * 400
    assert roofline.gram_bytes(9, "packed", 2, 3) == 5 * 2 + 4 * 6


def test_least_time_and_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    t, bound = roofline.gram_least_seconds(65536, 1024, "sign", "packed", p)
    assert bound == "compute" and t == pytest.approx(2 * 65536 * 1024 ** 2 / 393e12)
    t, bound = roofline.gram_least_seconds(4000, 20, "persymbol", "int8", p)
    assert bound == "memory" and t == pytest.approx((80000 + 1600) / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_lowered_operands_keep_their_bits():
    x = np.float32(1 + 2 ** -10 + 2 ** -20)
    assert tree.bf16(x) == 1.0  # 8 significant bits
    assert tree.high(x) == np.float32(1 + 2 ** -10)  # a bf16 pair: 16 bits
    assert tree.high(np.float32(1 + 2 ** -7)) == tree.bf16(np.float32(1 + 2 ** -7))


def test_reference_refuses_an_unknown_step():
    with pytest.raises(ValueError, match="no step"):
        tree.sweep(4, (8,), [("sign", 1)], 1, 0.4, 0.9, 0, lower=("mixer",))


def test_lowered_sampling_keeps_about_16_bits():
    rng = np.random.default_rng(0)
    z = rng.standard_normal((2, 6, 64)).astype(np.float32)
    parents = np.array([[0, 0, 1, 1, 2, 4]] * 2)
    rhos = rng.uniform(0.4, 0.9, (2, 6)).astype(np.float32)
    exact = tree.sample(z, parents, rhos)
    low = tree.sample(z, parents, rhos, lowered=True)
    assert low.dtype == np.float32 and exact.dtype == np.float64
    err = np.abs(low - exact).max() / np.abs(exact).max()
    assert 0 < err < 2 ** -13


def test_gap_sums_add_over_points():
    from bench.entries import run_trials as entry

    want = np.zeros((2, 3, 3), np.int64)
    got = want.copy()
    got[0, 0, :2] = [2, -5]
    got[1, 2, :2] = [-1, 4]
    got[1, 1, 2] = 99  # shared edges are not compared
    assert entry.gap_sums(got, want).tolist() == [3, 9]


def _mini_trace():
    # chip 0: ops [0,10) kernel, [5,20) fusion, [30,40) all-gather
    # (alone), [45,50) all-gather beside [44,60) fusion; window [0,100)
    ops = [["_sign_corr_kernel", 0, 10, "jit_f"], ["fusion.3", 5, 15, "jit_f"],
           ["all-gather.1", 30, 10, "jit_g"], ["all-gather.2", 45, 5, "jit_g"],
           ["fusion.9", 44, 16, "jit_g"]]
    mods = [["jit_f(7)", 0, 20], ["jit_g(8)", 30, 30]]
    spans = [["bench.run_trials", 0, 70], ["bench.learn_structure", 70, 25]]
    return {"window": [0, 100],
            "devices": [{"name": "/device:TPU:0", "ops": ops, "modules": mods}],
            "spans": spans}


def test_reduction_on_a_hand_made_trace():
    tr = _mini_trace()
    assert trace.window_ns(tr) == 100
    assert trace.busy_ns(tr) == 20 + 10 + 16
    assert trace.op_ns(tr, "_sign_corr_kernel") == 10
    assert trace.module_ns(tr, r"^jit_f(\(|$)") == 20
    assert trace.op_ns(tr, trace.COLLECTIVE.pattern) == 15
    assert trace.exposed_ns(tr) == 10  # all-gather.2 has a fusion beside it
    gaps = dict(trace.idle_gaps(tr))
    # idle [20,30), [40,44), [60,70) under run_trials, [70,95) under
    # learn_structure, [95,100) under no span
    assert gaps == pytest.approx({"bench.run_trials": 24e-9,
                                  "bench.learn_structure": 25e-9, "no span": 5e-9})
    ops = dict(trace.device_ops(tr))
    assert ops["jit_g/fusion"] == pytest.approx(16e-9)
    assert ops["jit_f/fusion"] == pytest.approx(15e-9)


def test_reduction_averages_over_chips():
    tr = _mini_trace()
    dev = dict(tr["devices"][0], name="/device:TPU:1",
               ops=[["fusion.1", 0, 50, "m"]])
    tr["devices"].append(dev)
    assert trace.busy_ns(tr) == (46 + 50) / 2


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.trace.json"))))
def test_reduction_on_a_recorded_trace(path):
    with open(path) as f:
        tr = json.load(f)
    w = trace.window_ns(tr)
    busy = trace.busy_ns(tr)
    assert 0 < busy <= w
    idle = sum(s for _, s in trace.idle_gaps(tr, top=100))
    assert idle * 1e9 == pytest.approx(w - busy, rel=1e-6)
    b = trace.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_benchmark_json_names_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    configs = {c["name"]: c for c in bench["configs"]}
    for wl in bench["workloads"]:
        assert name.match(wl["name"]) and len(wl["why"]) <= 200
        assert wl["config"] in configs and wl["chips"] in (1, 4)
        traffic = harness.load_json(harness.HERE, "traffic", wl["traffic"] + ".json")
        assert os.path.exists(os.path.join(harness.HERE, "entries",
                                           traffic["entry"] + ".py"))
        assert set(traffic["limits"]) <= {
            "error_gap_sum", "edit_gap_sum", "edges_differing"}
    for c in configs.values():
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(harness.HERE, "metrics", m["name"] + ".py"))
        for w in m["workloads"]:
            e2e_here = [x["name"] for x in harness.cell_metrics(bench, w, "end_to_end")]
            assert m["moves"] in e2e_here


def test_readers_return_nothing_without_events():
    tr = {"window": [0, 100], "devices": [{"name": "/device:TPU:0",
                                          "ops": [["fusion", 0, 10, "m"]],
                                          "modules": []}], "spans": []}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        if m["name"].startswith("device_idle"):
            continue
        w = m["workloads"][0]
        _, _, cfg, traffic = harness.cell_spec(w)
        reader = harness.load_module(
            os.path.join(harness.HERE, "metrics", m["name"] + ".py"), "r")
        ctx = {"trace": tr, "counters": {"sweeps": 1, "structures": 1, "reps": 8},
               "config": cfg, "traffic": traffic, "chips": 1,
               "peak": roofline.peaks("TPU v5 lite"), "window_s": 1.0}
        assert reader.read(ctx) is None, m["name"]


def _run_py(cwd, env_extra, args=("--workload", "fig3-sweep", "--seed", "1",
                                  "--seconds", "1", "--trace", "0")):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    r = _run_py(ROOT, {})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_refuses_a_forced_gram_backend():
    r = _run_py(ROOT, {"REPRO_GRAM_BACKEND": "xla"})
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_run_refuses_in_a_tree_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0 and r.stdout.strip() == ""
