"""The wire runtime (``distributed_learn_structure``) on a (1, 4) mesh of
forced CPU devices: the same trees as one device and the plain reference,
built once and reused, and its host spans.

One subprocess does the work (the main pytest process must keep the single
real CPU device) and prints what it saw as JSON; the tests read it."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
CASES = [(d, n, placement) for d in (32, 64) for n in (512, 1024)
         for placement in ("replicated", "rowblock")]
#: the spans of one call whose runtime is already built
CALL_SPANS = {"repro.distributed_learn_structure": 1, "repro.wire.place": 1,
              "repro.wire.weights": 1, "repro.structure.mst": 1,
              "repro.structure.fetch": 1, "repro.structure.edges": 1}

SCRIPT = """
import collections, glob, json, os, sys, tempfile
import numpy as np, jax, jax.monitoring
from jax.sharding import Mesh
from repro.core import Strategy, learn_structure
from repro.core.distributed import distributed_learn_structure
from repro.core.experiments import clear_compile_caches
from bench.entries import learn_structure as entry
from bench.reference import tree as ref

CASES = %r
mesh = Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model"))
events = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, d, **kw: events.append(e))
COMPILE = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
           "/jax/core/compile/backend_compile_duration")


def data(d, n, seed):
    parent, rho = ref.draw_trees(d, 1, 0.4, 0.9, seed)
    return entry._sample_fn(n, d)(jax.random.key(seed),
                                  jax.numpy.asarray(parent[0], "int32"),
                                  jax.numpy.asarray(rho[0]))


def edges(es):
    return sorted(tuple(sorted(e)) for e in es)


out = {"cases": {}}
for d, n, placement in CASES:
    x = data(d, n, 1000 * d + n)
    s = Strategy("sign", wire="packed", mst="boruvka", placement=placement)
    got = edges(distributed_learn_structure(x, mesh, strategy=s))
    one = edges(learn_structure(x, strategy=Strategy("sign", wire="packed",
                                                     mst="boruvka")))
    want = sorted(entry.reference_edges(np.asarray(x), {"method": "sign"}))
    out["cases"][f"{d},{n},{placement}"] = {
        "edges": len(got), "one_device": got == one, "reference": got == want}


def spans_of(call):
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    call()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    names = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        names[e.name] += 1
    return dict(names)


x = data(64, 1024, 7)
for placement in ("replicated", "rowblock"):
    s = Strategy("sign", wire="packed", mst="boruvka", placement=placement)
    clear_compile_caches()
    call = lambda: distributed_learn_structure(x, mesh, strategy=s)
    first = spans_of(call)
    del events[:]
    again = spans_of(call)
    compiles = sum(e in COMPILE for e in events)
    out[placement] = {"first": first, "again": again, "compiles": compiles}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def seen():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT % CASES)],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("d,n,placement", CASES)
def test_mesh_tree_equals_one_device_and_reference(seen, d, n, placement):
    case = seen["cases"][f"{d},{n},{placement}"]
    assert case["edges"] == d - 1
    assert case["one_device"] and case["reference"], case


@pytest.mark.parametrize("placement", ["replicated", "rowblock"])
def test_repeat_call_builds_and_compiles_nothing(seen, placement):
    got = seen[placement]
    assert got["compiles"] == 0
    assert "repro.wire.build" not in got["again"]


@pytest.mark.parametrize("placement", ["replicated", "rowblock"])
def test_traced_call_opens_each_span_once(seen, placement):
    got = seen[placement]
    assert got["again"] == CALL_SPANS
    assert got["first"] == {**CALL_SPANS, "repro.wire.build": 1}
