"""On-chip smoke test of the structure-learning path (TPU).

Drives the paper's pipeline once through the entry points a user calls —
``run_trials``, ``GramEngine`` / ``learn_structure`` and ``StructureServer``
— and checks every result against a plain reference: the host
``GramEngine(backend="numpy")`` contraction and host ``kruskal_mst``. It
prints each phase's checks and timings, exits non-zero at the first failed
check, and ends with one JSON line naming the device::

    python3 chip_smoke.py             # one chip: sweep, large-d Gram, serving
    python3 chip_smoke.py --chips 4   # four chips: wire mesh + tenant sharding

It fails without a TPU: nothing here falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

#: unit roundoff of f32
U32 = 2.0 ** -24


def check(ok, what: str, *, quiet: bool = False) -> None:
    """Print one passed check (unless ``quiet``), or stop the run at the
    first failed one."""
    if not ok:
        print(f"  FAIL {what}", flush=True)
        sys.exit(1)
    if not quiet:
        print(f"  ok   {what}", flush=True)


def f32_sum_bound(n: int) -> float:
    """gamma_n = n u / (1 - n u): the worst-case relative error of any
    f32 summation order over n products (Higham, Accuracy and Stability
    of Numerical Algorithms, eq. 3.4). Two such sums differ by at most
    twice it, times sum_i |u_i v_i| <= sqrt(G_jj G_kk) (Cauchy-Schwarz)."""
    return n * U32 / (1.0 - n * U32)


def gram_bound(g_ref, n: int):
    import numpy as np

    diag = np.sqrt(np.maximum(np.diagonal(g_ref, axis1=-2, axis2=-1), 0.0))
    return 2.0 * f32_sum_bound(n + 1) * diag[..., :, None] * diag[..., None, :]


# ---------------------------------------------------------------------------
# plain reference: host encode + numpy Gram + host Kruskal
# ---------------------------------------------------------------------------

def host_gram(xh, s):
    """The strategy's wire payload encoded on the host from the samples,
    and its Gram through the numpy engine: (payload, gram)."""
    import numpy as np

    from repro.core import GramEngine, PerSymbolQuantizer

    numpy_eng = GramEngine(backend="numpy")
    n = xh.shape[0]
    if s.method == "original":
        return xh, numpy_eng.gram(xh)
    if s.method == "sign":
        u = np.where(xh >= 0, 1, -1).astype(np.int8)
        if s.packed_gram_ok(n):
            bits = (u.T > 0).astype(np.uint8)
            p = np.packbits(bits, axis=-1, bitorder="little")
            return p, numpy_eng.packed_sign_gram(p, n)
        return u, numpy_eng.gram(u)
    q = PerSymbolQuantizer(s.rate)
    codes = np.searchsorted(np.asarray(q.boundaries), xh, side="left")
    codes = codes.astype(np.int8)
    return codes, numpy_eng.code_gram(codes, q.centroids_np)


def tree_of(adj) -> frozenset:
    import numpy as np

    iu, ju = np.nonzero(np.triu(np.asarray(adj), k=1))
    return frozenset(zip(iu.tolist(), ju.tolist()))


def kruskal_tree(w) -> frozenset:
    import numpy as np

    from repro.core import kruskal_mst

    return frozenset(tuple(sorted(e)) for e in kruskal_mst(np.asarray(w)))


def tree_weight(w, tree) -> float:
    return float(sum(float(w[j, k]) for j, k in tree))


def exact(s) -> bool:
    """Integer-exact Gram paths: sign (int8 or packed) and the rate-1
    code path, which the engine contracts as c^2 x a sign Gram."""
    return s.method == "sign" or (s.method == "persymbol" and s.rate == 1)


# ---------------------------------------------------------------------------
# phase 1: the paper's Fig. 3 sweep
# ---------------------------------------------------------------------------

def paper_sweep(d=20, ns=(125, 250, 500, 1000, 2000, 4000), reps=60,
                tree_ns=(125, 4000), tree_reps=8):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import (FIG3_STRATEGIES, GramEngine, Strategy, TrialPlan,
                            estimators, run_trials, sampler)
    from repro.core.experiments import stacked_trees, trial_keys

    print(f"[paper sweep] d={d} ns={ns} reps={reps}", flush=True)
    eng = GramEngine()
    print(f"  gram backend: {eng.resolve()}", flush=True)
    check(eng.resolve() == "pallas", "GramEngine() resolves to pallas")
    # the packed sign wire shares the label "sign" with the int8 wire, and
    # labels key a plan's results: it sweeps as a second plan over the
    # same trials (same seed, trees and samples)
    sweeps = []
    for strategies in (FIG3_STRATEGIES, (Strategy("sign", wire="packed"),)):
        plan = TrialPlan(d=d, ns=ns, strategies=strategies, reps=reps)
        t0 = time.perf_counter()
        run_trials(plan, engine=eng)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = run_trials(plan, engine=eng)
        warm = time.perf_counter() - t0
        print(f"  {len(strategies)} strategies: cold {cold:.3f}s  warm "
              f"{warm:.3f}s  compile ~{cold - warm:.3f}s  "
              f"({plan.trials / warm:.1f} trials/s warm)", flush=True)
        check(res.host_syncs == 1, "run_trials makes one host sync")
        for st in strategies:
            print(f"  {st.label}/{st.wire:7s} Pr(err) "
                  f"{res.error_rate[st.label]}", flush=True)
        sweeps.append((plan, res))
    (_, fig3), (_, packed) = sweeps
    check(all(getattr(packed, f)["sign"] == getattr(fig3, f)["sign"]
              for f in ("error_rate", "edit_distance", "edge_f1")),
          "packed sign sweep == int8 sign sweep, bit for bit")

    parents, rhos, adj_true = stacked_trees(sweeps[0][0])
    keys = trial_keys(sweeps[0][0])
    true_h = np.asarray(adj_true)
    ties = 0
    # the reference's Gram -> weights step is the estimator's own weight
    # function with n traced, as the sweep runs it: identical Grams then
    # give identical weights, ties included
    ref_weights = jax.jit(estimators.weights_from_gram, static_argnums=2)
    for i, n in enumerate(ns):
        x = sampler.sample_tree_ggm_rows_batch(keys, n, parents, rhos)
        xh = np.asarray(x)
        # every trial at this n: the sweep's error and edit counts must
        # equal the plain reference's on the integer paths
        for plan, res in sweeps:
            for s in filter(exact, plan.strategies):
                g_refs = np.stack([host_gram(xh[r], s)[1]
                                   for r in range(reps)])
                w_refs = np.asarray(ref_weights(
                    jnp.asarray(g_refs), jnp.float32(n), s))
                diffs = [len(kruskal_tree(w_refs[r]) ^ tree_of(true_h[r]))
                         for r in range(reps)]
                want = (sum(e > 0 for e in diffs), sum(diffs))
                got = (round(float(res.error_rate[s.label][i]) * reps),
                       round(float(res.edit_distance[s.label][i]) * reps))
                check(got == want, f"{s.label}/{s.wire} n={n}: sweep "
                                   f"errors/edits {got} == reference {want}")
        if n not in tree_ns:
            continue
        # per-trial payloads, Grams and trees on a subset of trials
        for s in (st for plan, _ in sweeps for st in plan.strategies):
            pipe = jax.jit(lambda xi, nf, s=s: _pipeline(xi, nf, s, eng))
            for r in range(tree_reps):
                payload, g, w, adj = pipe(x[r], jnp.float32(n))
                p_ref, g_ref = host_gram(xh[r], s)
                g = np.asarray(g)
                what = f"{s.label}/{s.wire} n={n} rep={r}"
                check(np.array_equal(np.asarray(payload), p_ref),
                      f"{what}: device payload == host encode", quiet=True)
                w_ref = np.asarray(ref_weights(
                    jnp.asarray(g_ref), jnp.float32(n), s))
                t_dev, t_ref = tree_of(adj), kruskal_tree(w_ref)
                if exact(s):
                    check(np.array_equal(g, g_ref) and t_dev == t_ref,
                          f"{what}: Gram and tree identical to the "
                          f"reference", quiet=True)
                    continue
                err = np.abs(g - g_ref)
                check((err <= gram_bound(g_ref, n)).all(),
                      f"{what}: Gram within the f32 bound (max err "
                      f"{err.max()})", quiet=True)
                if t_dev != t_ref:
                    # a tie: the device tree loses to the reference's by
                    # no more than the device/reference weight gap allows
                    off = ~np.eye(d, dtype=bool)
                    dw = float(np.abs(np.asarray(w) - w_ref)[off].max())
                    loss = tree_weight(w_ref, t_ref) - tree_weight(w_ref, t_dev)
                    check(loss <= 2 * len(t_dev - t_ref) * dw,
                          f"{what}: differing tree is an f32 tie (loss "
                          f"{loss}, weight gap {dw})", quiet=True)
                    ties += 1
        print(f"  n={n}: {tree_reps} trials per strategy: payload, Gram and "
              f"tree checked against the reference", flush=True)
    check(True, f"per-trial trees: integer paths identical, float paths "
                f"identical except {ties} f32 ties")


def _pipeline(x, n, s, eng):
    """One dataset through the device path: payload, Gram, weights, tree
    (``learn_structure_jit``'s stages, each returned for the checks; the
    sample count ``n`` is traced, as in the sweep)."""
    from repro.core import boruvka_mst, estimators

    payload = estimators.strategy_payload(x, s)
    g = estimators.payload_gram(payload, s, engine=eng)
    w = estimators.weights_from_gram(g, n, s)
    return payload, g, w, boruvka_mst(w)


# ---------------------------------------------------------------------------
# phase 2: large-d Gram
# ---------------------------------------------------------------------------

def large_d(d=4096, n=65536, d_code=1024, rate=4, block=256):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import (GramEngine, PerSymbolQuantizer, Strategy,
                            estimators, learn_structure, random_tree,
                            sampler)
    from repro.core.quantizers import sign_codes

    print(f"[large-d Gram] d={d} n={n}; R={rate} codes at d={d_code}",
          flush=True)
    eng, xla = GramEngine(), GramEngine(backend="xla")
    numpy_eng = GramEngine(backend="numpy", n_chunk=4096)
    print(f"  gram backend: {eng.resolve()}", flush=True)
    check(eng.resolve() == "pallas", "GramEngine() resolves to pallas")
    rng = np.random.default_rng(7)
    edges = random_tree(d, rng)
    x = sampler.sample_tree_ggm(jax.random.key(7), n, d, edges,
                                rng.uniform(0.4, 0.9, size=d - 1))
    u = sign_codes(x)
    packed = estimators.strategy_payload(x, Strategy("sign", wire="packed"))
    q = PerSymbolQuantizer(rate)
    codes = q.encode(x[:, :d_code]).astype(jnp.int8)
    print(f"  operands: int8 signs {u.nbytes >> 20} MiB, packed "
          f"{packed.nbytes >> 20} MiB, R{rate} codes {codes.nbytes >> 20} MiB",
          flush=True)

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        return out, first, time.perf_counter() - t0

    g_int8, c1, r1 = timed(eng.gram, u)
    g_pk, c2, r2 = timed(lambda p: eng.packed_sign_gram(p, n), packed)
    g_code, c3, r3 = timed(lambda c: eng.code_gram(c, q.centroids_np), codes)
    for name, first, run in (("int8 sign", c1, r1), ("packed sign", c2, r2),
                             (f"R{rate} code", c3, r3)):
        print(f"  pallas {name:11s} first call {first:.3f}s  "
              f"steady {run:.4f}s", flush=True)
    check(g_int8.shape == (d, d) and g_pk.shape == (d, d),
          f"Grams are ({d}, {d}) f32")
    check(np.array_equal(np.asarray(g_int8), np.asarray(xla.gram(u))),
          "int8 sign Gram: pallas == xla, bit for bit")
    check(np.array_equal(np.asarray(g_pk),
                         np.asarray(xla.packed_sign_gram(packed, n))),
          "packed sign Gram: pallas == xla, bit for bit")
    check(np.array_equal(np.asarray(g_pk), np.asarray(g_int8)),
          "packed sign Gram == int8 sign Gram")
    uh, ph = np.asarray(u), np.asarray(packed)
    a, b = d // 2, d - block
    blk = np.asarray(g_int8[a:a + block, b:b + block])
    check(np.array_equal(blk, numpy_eng.gram(uh[:, a:a + block],
                                             uh[:, b:b + block])),
          f"int8 sign Gram block [{a}:{a + block}, {b}:{b + block}] == numpy")
    check(np.array_equal(np.asarray(g_pk[a:a + block, b:b + block]),
                         numpy_eng.packed_sign_gram(ph[a:a + block], n,
                                                    ph[b:b + block])),
          f"packed sign Gram block [{a}:{a + block}, {b}:{b + block}] == numpy")
    g_code_ref = numpy_eng.code_gram(np.asarray(codes), q.centroids_np)
    err = np.abs(np.asarray(g_code) - g_code_ref)
    bound = gram_bound(g_code_ref, n)
    print(f"  R{rate} Gram: max |pallas - numpy| {err.max():.6g}, "
          f"max err/bound {float((err / bound).max()):.3g}", flush=True)
    check((err <= bound).all(),
          f"R{rate} code Gram within the f32 bound 2 gamma_(n+1) "
          f"sqrt(G_jj G_kk) of numpy")

    t0 = time.perf_counter()
    tree = learn_structure(x, strategy=Strategy("sign", wire="packed"),
                           engine=eng)
    wall = time.perf_counter() - t0
    t_dev = frozenset(tuple(sorted(e)) for e in tree)
    w = estimators.weights_from_gram(g_pk, n, "sign")
    t_ref = kruskal_tree(w)
    true = frozenset(tuple(sorted(e)) for e in edges)
    print(f"  learn_structure {wall:.3f}s; edges recovered "
          f"{len(t_dev & true)}/{d - 1}", flush=True)
    check(len(t_dev) == d - 1 and t_dev == t_ref,
          "learn_structure tree == host Kruskal on the Gram")
    return packed, codes, q.centroids_np, n


def custom_call_check(packed, codes, centroids, n) -> None:
    import jax

    from repro.core import GramEngine

    eng = GramEngine()
    for name, fn, arg in (
            ("packed sign", lambda p: eng.packed_sign_gram(p, n), packed),
            ("code", lambda c: eng.code_gram(c, centroids), codes)):
        hlo = jax.jit(fn).lower(arg).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"compiled {name} Gram step contains tpu_custom_call")


# ---------------------------------------------------------------------------
# phase 3: the serving plane
# ---------------------------------------------------------------------------

#: 64 tenants of 4 machines each, sign payloads, light wire pathologies
TRAFFIC = dict(tenants=64, machines=4, ticks=20, n=48, d=32,
               p_duplicate=0.05, p_reorder=0.05, p_drop=0.02, seed=3)
SERVE = dict(tenants=64, machines=4, d=32, block_n=48, snapshot_every=4,
             reorder_ticks=2, fold_budget=64 * 8, queue_capacity=64 * 16)


def drive(srv, trace, extra_ticks: int = 6) -> list[float]:
    walls = []
    for batch in trace + [[]] * extra_ticks:
        t0 = time.perf_counter()
        for p in batch:
            srv.submit(p)
        srv.run_tick()
        walls.append(time.perf_counter() - t0)
    srv.force_resolve()
    return walls


def serving(traffic=TRAFFIC, serve=SERVE):
    import numpy as np
    import jax.numpy as jnp

    from repro.core import GramEngine, StreamingGram
    from repro.serve import (ServeConfig, StructureServer, TrafficConfig,
                             make_trace, unique_payloads)

    print(f"[serving] {traffic['tenants']} tenants d={traffic['d']} "
          f"{traffic['ticks']} ticks", flush=True)
    trace = make_trace(TrafficConfig(**traffic))
    with tempfile.TemporaryDirectory() as wd:
        srv = StructureServer(ServeConfig(**serve), wd)
        walls = drive(srv, trace)
        print(f"  first tick {walls[0]:.3f}s  later ticks mean "
              f"{float(np.mean(walls[1:])):.4f}s", flush=True)
        refs = {}
        numpy_eng = GramEngine(backend="numpy")
        for p in unique_payloads(trace):
            sg = refs.setdefault(p.tenant, StreamingGram(
                d=traffic["d"], method="sign", engine=numpy_eng))
            if p.kind == "codes":
                sg.update_codes(jnp.asarray(p.codes))
            else:
                sg.update_packed(jnp.asarray(p.packed), p.n)
        check(all(np.array_equal(np.asarray(sg.gram, np.float64),
                                 srv.table.gram[t])
                  and sg.n == int(srv.table.n[t]) for t, sg in refs.items()),
              f"folds exactly once: {len(refs)} tenants == numpy "
              f"StreamingGram reference")
        check(srv.log.buffered() == 0, "reorder buffers drained")
        edges = srv.table.adj.sum(axis=(1, 2)) // 2
        check(bool((edges[srv.table.n > 0] == traffic["d"] - 1).all()),
              "every tenant with data has a spanning tree")
        srv.close()


# ---------------------------------------------------------------------------
# four chips: wire mesh and tenant sharding vs one device
# ---------------------------------------------------------------------------

def four_chips(sizes=((20, (125, 1000, 4000), 8), (1024, (16384,), 4))):
    import numpy as np
    import jax

    from repro.core import (FIG3_STRATEGIES, MACChannel, Strategy, TrialPlan,
                            run_trials)
    from repro.launch.mesh import make_trial_mesh
    from repro.serve import ServeConfig, StructureServer, TrafficConfig, \
        make_trace

    count = len(jax.devices())
    check(count == 4, f"4 devices visible (got {count})")
    # the packed sign wire sweeps as its own plan: it shares the label
    # "sign" with the int8 wire
    groups = (FIG3_STRATEGIES + (Strategy("sign", channel=MACChannel(4)),),
              (Strategy("sign", wire="packed"),))
    for (d, ns, reps), strategies in (
            (size, g) for size in sizes for g in groups):
        plan = TrialPlan(d=d, ns=ns, strategies=strategies, reps=reps)
        print(f"[wire mesh] d={d} ns={ns} reps={reps} "
              f"{[st.label + '/' + st.wire for st in strategies]}",
              flush=True)
        t0 = time.perf_counter()
        one = run_trials(plan)
        print(f"  one device {time.perf_counter() - t0:.3f}s", flush=True)
        check(one.host_syncs == 1 and one.mesh_devices == 1,
              "one-device sweep: one host sync")
        for data, model in ((1, 4), (2, 2)):
            mesh = make_trial_mesh(data, model=model)
            ids = {dv.id for dv in mesh.devices.flat}
            check(len(ids) == 4, f"({data}, {model}) mesh spans 4 distinct "
                                 f"devices {sorted(ids)}")
            t0 = time.perf_counter()
            res = run_trials(plan, mesh=mesh)
            print(f"  ({data}, {model}) mesh {time.perf_counter() - t0:.3f}s",
                  flush=True)
            same = all(
                np.array_equal(getattr(res, f)[s.label],
                               getattr(one, f)[s.label])
                for s in strategies
                for f in ("error_rate", "edit_distance", "edge_f1"))
            check(same and res.host_syncs == 1 and res.mesh_devices == 4,
                  f"({data}, {model}) mesh == one device, bit for bit, "
                  f"one host sync")
        print("  " + "  ".join(f"{s.label}:{one.error_rate[s.label]}"
                               for s in strategies), flush=True)

    print("[tenant sharding] serving with use_mesh=True vs one device",
          flush=True)
    trace = make_trace(TrafficConfig(**TRAFFIC))
    states = []
    with tempfile.TemporaryDirectory() as wd:
        for use_mesh in (False, True):
            srv = StructureServer(ServeConfig(**SERVE, use_mesh=use_mesh),
                                  os.path.join(wd, str(use_mesh)))
            if use_mesh:
                placed = srv.table._place(np.zeros(
                    (SERVE["tenants"], SERVE["d"], SERVE["d"]), np.float32))
                check(len(placed.sharding.device_set) == 4,
                      "tenant-batched operands span 4 distinct devices")
            t0 = time.perf_counter()
            drive(srv, trace)
            print(f"  use_mesh={use_mesh} {time.perf_counter() - t0:.3f}s",
                  flush=True)
            states.append(srv.comparable_state())
            srv.close()
    check(all(np.array_equal(states[0][k], states[1][k]) for k in states[0]),
          "tenant-sharded server state == one-device server, bit for bit")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args()
    if os.environ.get("REPRO_GRAM_BACKEND"):
        print("REPRO_GRAM_BACKEND is set: the smoke test needs the engine's "
              "own backend choice", file=sys.stderr)
        return 1
    import jax

    import repro.core  # noqa: F401 — fails here, before the chip, outside the repo

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(HERE, ".jax_cache"))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax.devices()[0] is {dev.platform}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        paper_sweep()
        custom_call_check(*large_d())
        serving()
    print(f"all checks passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
