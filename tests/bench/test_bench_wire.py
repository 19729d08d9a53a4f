"""The d1024-wire-m4 cell: its readers on hand-made four-chip traces, its
control, and the cell itself at a small size on four forced CPU devices
(one subprocess: the main pytest process keeps its single CPU device).
The sound program passes the check; a machine whose payload is lost
fails it; a traced run opens the program's spans once a structure."""
import collections
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness, roofline, roofline_wire, trace

WORKLOAD = "d1024-wire-m4"
SMALL = {"d": 32, "n": 512, "datasets": 2}
PEAK = roofline.peaks("TPU v5 lite")
MS = 1_000_000  # ns


def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "metrics", name + ".py"),
        "r_" + name.replace(".", "_"))


def _ctx(tr, structures=2):
    _, _, cfg, traffic = harness.cell_spec(WORKLOAD)
    report_bytes = cfg["n"] * cfg["d"] // 8  # the packed sign payload
    return {"trace": tr, "config": cfg, "traffic": traffic, "peak": PEAK,
            "chips": 4, "counters": {"structures": structures,
                                     "wire_bytes": report_bytes}}


def _chip(i, ops):
    return {"name": f"/device:TPU:{i}", "modules": [],
            "ops": [[name, s, d, "jit_local_weights"] for name, s, d in ops]}


def _trace(kernel_ns):
    """Two structures in a 40-ms window on four chips. Per structure and
    chip: encode [t, t+0.5 ms), the payload all-gather [t+0.5, t+0.6 ms),
    the Gram kernel for ``kernel_ns``, the weights for 0.2 ms. On chip 3
    a copy runs beside the first structure's gather for its last 40 us."""
    chips = []
    for i in range(4):
        ops = []
        for t in (0, 20 * MS):
            g = t + 600_000
            ops += [["fusion.3", t, 500_000],
                    ["all-gather.4", t + 500_000, 100_000],
                    ["sign_corr_packed.1", g, kernel_ns],
                    ["negate_subtract_fusion", g + kernel_ns, 200_000]]
        if i == 3:
            ops.append(["copy.2", 560_000, 40_000])
        chips.append(_chip(i, ops))
    return {"window": [0, 40 * MS], "devices": chips, "spans": []}


REPLICATED = 9_300_000  # the whole Gram on every chip
ROWBLOCK = 2_400_000  # a quarter of it on each


@pytest.mark.parametrize("kernel_ns", [REPLICATED, ROWBLOCK],
                         ids=["replicated", "rowblock"])
def test_wire_readers_on_a_hand_made_four_chip_trace(kernel_ns):
    ctx = _ctx(_trace(kernel_ns))
    # 2 gathers of 100 us on each chip: 0.1 ms a structure
    assert _reader("wire_ms.wire").read(ctx) == pytest.approx(0.1, abs=1e-12)
    # chips 0-2: 200 us exposed; chip 3: 160 us; mean 190 us over 2
    assert trace.exposed_ns(ctx["trace"]) == 190_000
    assert _reader("wire_exposed_ms.wire").read(ctx) == pytest.approx(
        0.095, abs=1e-12)
    busy = 2 * (600_000 + kernel_ns + 200_000)
    assert _reader("device_idle.wire").read(ctx) == pytest.approx(
        100 * (1 - busy / (40 * MS)))
    # least time of the wire: 6 MiB received a chip at 200 GB/s
    least = (65536 * 1024 // 8) * 3 / 4 / 200e9
    wire = _reader("wire_roofline.wire").read(ctx)
    assert wire == pytest.approx(100 * least / 100e-6)
    # one Gram a structure, on four chips' peaks, over the kernel per chip
    gram_least = 2 * 65536 * 1024 ** 2 / PEAK["int8_ops"] / 4
    gram = _reader("gram_roofline.wire").read(ctx)
    assert gram == pytest.approx(100 * gram_least / (kernel_ns / 1e9))
    assert 0 < wire <= 100 and 0 < gram <= 100


def test_replicated_gram_reads_a_quarter_of_one_chip():
    ctx = _ctx(_trace(REPLICATED))
    one_chip = harness.load_module(
        os.path.join(harness.HERE, "metrics", "gram_roofline.structure.py"),
        "r_gram_structure")
    single = {**ctx, "counters": {"structures": 2}, "chips": 1}
    assert _reader("gram_roofline.wire").read(ctx) == pytest.approx(
        one_chip.read(single) / 4)


def test_wire_readers_on_a_recorded_four_chip_trace():
    """150 ms of a traced run of the cell on a v5e-4 (9 structures, the
    replicated placement): every chip's trace holds the ops the readers
    match, and both shares stay under their rooflines."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "d1024-wire-m4.four-chip.json")) as f:
        tr = json.load(f)
    structures = sum(name == "bench.distributed_learn_structure"
                     for name, _, _ in tr["spans"])
    ctx = _ctx(tr, structures)
    kernels = _reader("gram_roofline.wire").KERNELS
    assert len(tr["devices"]) == 4
    for dev in tr["devices"]:
        one = dict(tr, devices=[dev])
        assert trace.op_ns(one, trace.COLLECTIVE.pattern) > 0
        assert trace.op_ns(one, kernels) > 0
    wire = _reader("wire_ms.wire").read(ctx)
    assert 0 < _reader("wire_exposed_ms.wire").read(ctx) <= wire
    assert 0 < _reader("wire_roofline.wire").read(ctx) <= 100
    gram = _reader("gram_roofline.wire").read(ctx)
    assert 0 < gram <= 100
    # every chip computes the whole Gram: a quarter of one chip's share
    one_chip = harness.load_module(
        os.path.join(harness.HERE, "metrics", "gram_roofline.structure.py"),
        "r_gram_structure")
    assert gram == pytest.approx(one_chip.read({**ctx, "chips": 1}) / 4)


def test_wire_least_times():
    assert roofline_wire.received_bytes(8 << 20, 4) == 6 << 20
    assert roofline_wire.wire_least_seconds(8 << 20, 4, PEAK) == \
        pytest.approx((6 << 20) / 200e9)
    t, _ = roofline.gram_least_seconds(65536, 1024, "sign", "packed", PEAK)
    assert roofline_wire.gram_least_seconds(
        65536, 1024, "sign", "packed", PEAK, 4) == pytest.approx(t / 4)


def test_wire_readers_need_the_payload_counter():
    ctx = _ctx(_trace(REPLICATED))
    ctx["counters"] = {"structures": 2}
    assert _reader("wire_roofline.wire").read(ctx) is None


def test_wire_control_fails_the_limit():
    # int16 counts wrap only past 32767 samples: the cell's own n
    _, _, cfg, traffic = harness.cell_spec(WORKLOAD)
    mod = harness.load_module(
        os.path.join(harness.HERE, "entries", traffic["entry"] + ".py"),
        "e_ds")
    gap = mod.control(dict(cfg, d=32), traffic, 5)["edges_differing"]
    assert gap > traffic["limits"]["edges_differing"]


SCRIPT = """
import collections, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from bench import harness, spans
from repro.core import Strategy, distributed
from repro.core.distributed import WirePlan

SMALL = %r
out = {}
r = harness.run(%r, 2**31 + 11, 0.5, False, require_chip=False,
                overrides=SMALL)
out["sound"] = {"correct": r["correct"], "checks": r["checks"],
                "structure_s": r["metrics"]["structure_s"]["value"],
                "device": r["device"]}

encode = WirePlan.encode


def lost(self, x_loc, **kw):
    payload = encode(self, x_loc, **kw)
    first = jax.lax.axis_index(self.model_axis) == 0
    return jnp.where(first, jnp.zeros_like(payload), payload)


WirePlan.encode = lost
distributed._wire_runtime.cache_clear()
r = harness.run(%r, 2**31 + 12, 0.5, False, require_chip=False,
                overrides=SMALL)
out["lost"] = {"correct": r["correct"], "checks": r["checks"]}
WirePlan.encode = encode
distributed._wire_runtime.cache_clear()

r, tr = spans.traced(%r, 2**31 + 13, 0.5, require_chip=False,
                     overrides=SMALL)
roots = [(s, s + d) for name, s, d in tr["spans"]
         if name == "bench.distributed_learn_structure"]
inside = all(any(a <= s and s + d <= b for a, b in roots)
             for name, s, d in tr["spans"] if name.startswith("repro."))
out["traced"] = {"correct": r["correct"], "calls": r["attempted"],
                 "roots": len(roots), "inside": inside,
                 "counts": collections.Counter(n for n, _, _ in tr["spans"])}

fn, sharding = distributed.build_weights_fn(
    Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model")),
    strategy=Strategy("sign", wire="packed"))
out["module"] = fn.lower(jax.ShapeDtypeStruct(
    (512, 32), jnp.float32, sharding=sharding)).as_text().split(
    "module @", 1)[1].split()[0]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([harness.ROOT,
                                           os.path.join(harness.ROOT, "src")]))
    script = textwrap.dedent(SCRIPT % (SMALL, WORKLOAD, WORKLOAD, WORKLOAD))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=env, cwd=harness.ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_wire_cell_correct_at_small_size(cell):
    got = cell["sound"]
    assert got["correct"], got["checks"]
    assert got["checks"]["edges_differing"]["value"] == 0
    assert got["structure_s"] > 0 and got["device"]["count"] == 4


def test_wire_cell_lost_machine_fails(cell):
    got = cell["lost"]
    assert not got["correct"]
    assert got["checks"]["edges_differing"]["value"] > 0


def test_wire_cell_traced_spans_once_a_structure(cell):
    got = cell["traced"]
    calls = got["calls"]
    assert got["correct"] and calls >= 1 and got["roots"] == calls
    assert got["inside"]
    want = {name: calls for name in (
        "bench.distributed_learn_structure",
        "repro.distributed_learn_structure", "repro.wire.place",
        "repro.wire.weights", "repro.structure.mst",
        "repro.structure.fetch", "repro.structure.edges")}
    assert collections.Counter(got["counts"]) == collections.Counter(want)


def test_wire_runtime_module_name(cell):
    # the name a device trace gives the runtime's ops as their module
    assert cell["module"] == "jit_local_weights"
