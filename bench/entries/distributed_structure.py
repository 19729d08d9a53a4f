"""Window loop: a closed loop of ``distributed_learn_structure``, one
request at a time, over datasets held by the machines of a (1, M) mesh.

The paper's deployment (arXiv 1809.08067, sections 3-4): machine j of the
configuration's ``machines`` is device j of the cell, and holds features
[j d/M, (j+1) d/M) of all n samples. Set-up draws the same datasets as the
``learn_structure`` loop (the same seeds and sampler, from that entry) and
places each as ``P("data", "model")``, so each device holds only its
machine's block. Each structure encodes on the machines, gathers the
payload over the mesh and learns the tree centrally.

``structure_s`` is the window's length over the structures completed.
The counters add, per structure, the wire's payload from
``WirePlan.comm_report``: the bytes gathered, the paper's logical bits and
the collectives issued. The check is the ``learn_structure`` loop's: every
answer against its dataset's reference tree (``edges_differing``).
"""
from __future__ import annotations

import os
import time

import numpy as np

from bench import harness, seeds
from bench.reference import tree as ref

_ls = harness.load_module(os.path.join(harness.HERE, "entries",
                                       "learn_structure.py"),
                          "bench_entry_learn_structure_for_wire")
control = _ls.control


class Cell(_ls.Cell):
    def setup(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import Strategy
        from repro.core.distributed import (WirePlan,
                                            distributed_learn_structure)

        cfg = self.cfg
        n, d, m = cfg["n"], cfg["d"], cfg["machines"]
        self.mesh = Mesh(np.array(self.devices[:m]).reshape(1, m),
                         ("data", "model"))
        self.strategy = Strategy(cfg["method"], wire=cfg["wire"],
                                 mst=cfg["mst"])
        self.learn = distributed_learn_structure
        report = WirePlan(self.strategy).comm_report(n, d)
        self.wire = {"wire_bytes": report.wire_bytes,
                     "logical_bits": report.logical_bits,
                     "collectives": report.collectives}
        placed = NamedSharding(self.mesh, P("data", "model"))
        draw = _ls._sample_fn(n, d)
        self.xs = []
        with self.span("data"):
            for k in range(self.traffic["datasets"]):
                s = seeds.derive(self.seed, 3, k)
                parent, rho = ref.draw_trees(d, 1, cfg["rho_min"],
                                             cfg["rho_max"], s)
                x = draw(jax.random.key(s),
                         jax.numpy.asarray(parent[0], "int32"),
                         jax.numpy.asarray(rho[0]))
                self.xs.append(jax.device_put(x, placed))
                del x
            jax.block_until_ready(self.xs)
        with self.span("warmup"):
            self.learn(self.xs[0], self.mesh, strategy=self.strategy)

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            k = i % len(self.xs)
            with self.span("distributed_learn_structure"):
                edges = self.learn(self.xs[k], self.mesh,
                                   strategy=self.strategy)
            self.answers.append((k, frozenset(tuple(sorted(e)) for e in edges)))
            i += 1
        elapsed = time.perf_counter() - t0
        self.counters = {"structures": i, "elapsed_s": elapsed, **self.wire}
        return {"metrics": {"structure_s": elapsed / i},
                "counters": self.counters, "attempted": i, "failed": 0}
