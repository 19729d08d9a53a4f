"""Window loop: a closed loop of ``learn_structure``, one request at a
time, over datasets made on the device in set-up and cycled through.

Set-up draws ``datasets`` trees of the configuration (Pruefer trees, edge
correlations Uniform[rho_min, rho_max]) and their (n, d) float32 samples
on the device in one jitted call each. ``structure_s`` is the window's
length over the structures completed; the window ends at a structure
boundary. The check computes each dataset's tree once through the plain
reference and compares every answer the window returned (``checks``: the
most edges by which any answer differs from its dataset's reference
tree).
"""
from __future__ import annotations

import time

import numpy as np

from bench import seeds
from bench.reference import tree as ref


def _sample_fn(n: int, d: int):
    """jit: (key, parent, rho) -> (n, d) f32 tree-GGM samples on the
    device, x = (c * z) M^T with M the path-product mixer, at HIGHEST."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def f(key, parent, rho):
        t = jnp.arange(d)
        B = jnp.zeros((d, d), jnp.float32).at[t, parent].set(
            jnp.where(t > 0, rho, 0.0))
        M = jnp.eye(d, dtype=jnp.float32) + B
        P = B
        for _ in range(max(int(np.ceil(np.log2(d))), 1)):
            P = jnp.matmul(P, P, precision=hi)
            M = M + jnp.matmul(M, P, precision=hi)
        c = jnp.sqrt(jnp.clip(1.0 - rho * rho, 0.0, None)).at[0].set(1.0)
        z = jax.random.normal(key, (n, d), jnp.float32)
        return jnp.matmul(z * c[None, :], M.T, precision=hi)

    return jax.jit(f)


class Cell:
    def __init__(self, cfg, traffic, seed, devices, spans):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices, self.span = devices, spans
        self.answers: list[tuple[int, frozenset]] = []
        self.counters: dict = {}

    def setup(self):
        import jax
        from repro.core import Strategy, learn_structure

        cfg = self.cfg
        n, d = cfg["n"], cfg["d"]
        self.strategy = Strategy(cfg["method"], wire=cfg["wire"],
                                 mst=cfg["mst"])
        self.learn = learn_structure
        draw = _sample_fn(n, d)
        self.xs = []
        with self.span("data"):
            for k in range(self.traffic["datasets"]):
                s = seeds.derive(self.seed, 3, k)
                parent, rho = ref.draw_trees(d, 1, cfg["rho_min"],
                                             cfg["rho_max"], s)
                self.xs.append(draw(jax.random.key(s),
                                    jax.numpy.asarray(parent[0], "int32"),
                                    jax.numpy.asarray(rho[0])))
            jax.block_until_ready(self.xs)
        with self.span("warmup"):
            self.learn(self.xs[0], strategy=self.strategy)

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            k = i % len(self.xs)
            with self.span("learn_structure"):
                edges = self.learn(self.xs[k], strategy=self.strategy)
            self.answers.append((k, frozenset(tuple(sorted(e)) for e in edges)))
            i += 1
        elapsed = time.perf_counter() - t0
        self.counters = {"structures": i, "elapsed_s": elapsed}
        return {"metrics": {"structure_s": elapsed / i},
                "counters": self.counters, "attempted": i, "failed": 0}

    def release(self):
        import jax

        self.host = [np.asarray(x) for x in self.xs]
        self.xs = self.learn = None
        jax.clear_caches()

    def check(self) -> list[tuple[str, float, float]]:
        worst = 0
        for k, x in enumerate(self.host):
            want = reference_edges(x, self.cfg)
            for kk, got in self.answers:
                if kk == k:
                    worst = max(worst, len(got ^ want))
        self.host = None
        return [("edges_differing", float(worst),
                 self.traffic["limits"]["edges_differing"])]


def reference_edges(x: np.ndarray, cfg: dict, **kw) -> frozenset:
    """The reference tree of one (n, d) dataset, as (j, k), j < k, edges."""
    n = x.shape[0]
    adj = ref.structure(np.ascontiguousarray(x.T)[None], n, cfg["method"],
                        cfg.get("rate", 1), **kw)[0]
    iu, ju = np.nonzero(np.triu(adj, 1))
    return frozenset(zip(iu.tolist(), ju.tolist()))


def control(cfg: dict, traffic: dict, seed: int) -> dict:
    """The control's reading on one dataset drawn from ``seed``: the
    reference in the program's place computed as the traffic's
    ``control`` says (``{"counts": "int16"}``: sign counts accumulated in
    int16, the nearest integer precision below the int32 the
    configuration states): edges by which its tree differs."""
    import jax

    n, d = cfg["n"], cfg["d"]
    s = seeds.derive(seed, 3, 0)
    parent, rho = ref.draw_trees(d, 1, cfg["rho_min"], cfg["rho_max"], s)
    x = np.asarray(_sample_fn(n, d)(jax.random.key(s),
                                    jax.numpy.asarray(parent[0], "int32"),
                                    jax.numpy.asarray(rho[0])))
    want = reference_edges(x, cfg)
    got = reference_edges(x, cfg, **traffic["control"])
    return {"edges_differing": len(got ^ want)}
