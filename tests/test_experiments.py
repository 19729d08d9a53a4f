"""On-device trial plane: Strategy API, vmapped MWST, device metrics,
batched sampler, and run_trials parity with the reference loop."""
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import chow_liu as CL
from repro.core import estimators, sampler, trees
from repro.core.experiments import (TrialPlan, clear_compile_caches,
                                    compile_cache_size, evaluate_strategies,
                                    mc_persymbol_corr_error,
                                    mc_sign_crossover, next_pow2, run_trials,
                                    stacked_trees, trial_keys)
from repro.core.strategy import FIG3_STRATEGIES, Strategy, as_strategy
from repro.core.streaming import StreamingGram


# --------------------------------------------------------------------------
# Strategy API
# --------------------------------------------------------------------------

def test_strategy_labels_and_normalization():
    assert Strategy("sign").label == "sign"
    assert Strategy("persymbol", rate=3).label == "R3"
    assert Strategy("original").label == "original"
    # sign forces rate 1; original forces the float32 wire
    assert Strategy("sign", rate=5).rate == 1
    assert Strategy("original").wire == "float32"
    assert [s.label for s in FIG3_STRATEGIES] == [
        "sign", "R1", "R2", "R3", "R4", "original"]


def test_strategy_validation():
    with pytest.raises(ValueError):
        Strategy("nope")
    with pytest.raises(ValueError):
        Strategy("persymbol", rate=9)
    with pytest.raises(ValueError):
        Strategy("persymbol", rate=3, wire="packed")  # 3 does not divide 8
    with pytest.raises(ValueError):
        Strategy("sign", wire="float32")  # float32 wire == original
    with pytest.raises(ValueError):
        Strategy("sign", mst="prim")


def test_strategy_is_hashable_and_comm_bits():
    assert len({Strategy("sign"), Strategy("sign"), Strategy("original")}) == 2
    # communication_bits is wire-honest: the paper's n*d*R only on the
    # dense packed wire; int8 spends a byte per code, float32 a float
    assert Strategy("persymbol", rate=4,
                    wire="packed").communication_bits(100, 8) == 3200
    assert Strategy("persymbol", rate=4).communication_bits(100, 8) == 6400
    assert Strategy("sign", wire="packed").communication_bits(100, 8) == 800
    assert Strategy("original").communication_bits(100, 8) == 25600
    assert as_strategy(Strategy("sign")).label == "sign"
    assert as_strategy(None, method="persymbol", rate=2).label == "R2"


# --------------------------------------------------------------------------
# Device tree machinery vs host reference
# --------------------------------------------------------------------------

def _random_tree_arrays(d, seed):
    rng = np.random.default_rng(seed)
    edges = trees.random_tree(d, rng)
    w = rng.uniform(0.2, 0.9, size=d - 1)
    parent, rho, perm = trees.topological_parents(d, edges, w)
    return edges, w, parent, rho, perm


@pytest.mark.parametrize("d,seed", [(2, 0), (7, 1), (20, 2), (33, 3)])
def test_tree_correlation_matches_host(d, seed):
    edges, w, parent, rho, perm = _random_tree_arrays(d, seed)
    Qh = trees.tree_correlation_matrix(d, edges, w)
    Qd = np.asarray(trees.tree_correlation(jnp.asarray(parent),
                                           jnp.asarray(rho)))
    assert np.abs(Qd - Qh[np.ix_(perm, perm)]).max() < 1e-5


def test_adjacency_from_parents_matches_host():
    d = 14
    edges, w, parent, rho, perm = _random_tree_arrays(d, 5)
    adj_d = np.asarray(trees.adjacency_from_parents(jnp.asarray(parent)))
    adj_h = trees.tree_adjacency(d, edges)[np.ix_(perm, perm)]
    assert (adj_d == adj_h).all()


def test_device_metrics_match_tree_edit_distance():
    d = 12
    for sa, sb in [(0, 0), (0, 1), (2, 3), (4, 4)]:
        ea = trees.random_tree(d, np.random.default_rng(sa))
        eb = trees.random_tree(d, np.random.default_rng(sb))
        aa = jnp.asarray(trees.tree_adjacency(d, ea))
        ab = jnp.asarray(trees.tree_adjacency(d, eb))
        ted = trees.tree_edit_distance(ea, eb)
        assert int(trees.structure_hamming(aa, ab)) == ted
        assert bool(trees.structure_error(aa, ab)) == (ted > 0)
        if ted == 0:
            assert float(trees.edge_f1(aa, ab)) == pytest.approx(1.0)
        else:
            assert float(trees.edge_f1(aa, ab)) < 1.0


def test_device_metrics_batch_over_leading_axis():
    d = 9
    adjs, trues = [], []
    for s in range(4):
        ea = trees.random_tree(d, np.random.default_rng(s))
        eb = trees.random_tree(d, np.random.default_rng(s + 10))
        adjs.append(trees.tree_adjacency(d, ea))
        trues.append(trees.tree_adjacency(d, eb))
    A, B = jnp.asarray(np.stack(adjs)), jnp.asarray(np.stack(trues))
    ham = trees.structure_hamming(A, B)
    assert ham.shape == (4,)
    for i in range(4):
        assert int(ham[i]) == int(trees.structure_hamming(A[i], B[i]))


# --------------------------------------------------------------------------
# vmapped Boruvka vs per-matrix Kruskal (satellite requirement)
# --------------------------------------------------------------------------

def test_vmap_boruvka_matches_kruskal():
    d, b = 14, 9
    rng = np.random.default_rng(42)
    ws = []
    for _ in range(b - 2):
        w = rng.normal(size=(d, d))
        w = (w + w.T) / 2
        np.fill_diagonal(w, 0.0)
        ws.append(w)
    ws.append(np.ones((d, d)) - np.eye(d))           # total tie-break stress
    w = rng.integers(0, 3, size=(d, d)).astype(float)  # many duplicate ranks
    ws.append((w + w.T) / 2)
    W = jnp.asarray(np.stack(ws))
    adjs = np.asarray(jax.jit(jax.vmap(CL.boruvka_mst))(W))
    for i in range(b):
        ek = trees.edges_canonical(CL.kruskal_mst(np.asarray(W[i])))
        eb = trees.edges_canonical(CL.adjacency_to_edges(adjs[i]))
        assert ek == eb, f"batch element {i} disagrees"
        assert trees.is_tree(d, CL.adjacency_to_edges(adjs[i]))


def test_kruskal_mst_is_forest_special_case():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(10, 10))
    w = (w + w.T) / 2
    assert CL.kruskal_mst(w) == CL.kruskal_forest(w, min_weight=-np.inf)


# --------------------------------------------------------------------------
# Batched sampler
# --------------------------------------------------------------------------

def test_batched_sampler_matches_tree_correlation():
    d, n, t = 8, 60_000, 3
    parents, rhos = [], []
    for s in range(t):
        _, _, parent, rho, _ = _random_tree_arrays(d, s)
        parents.append(parent)
        rhos.append(rho)
    P = jnp.asarray(np.stack(parents))
    R = jnp.asarray(np.stack(rhos))
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.key(0), jnp.arange(t, dtype=jnp.uint32))
    x = np.asarray(sampler.sample_tree_ggm_batch(keys, n, P, R))
    assert x.shape == (t, n, d)
    for i in range(t):
        Q = np.asarray(trees.tree_correlation(P[i], R[i]))
        emp = np.corrcoef(x[i].T)
        assert np.abs(emp - Q).max() < 0.04
    # distinct keys -> distinct draws
    assert np.abs(x[0] - x[1]).max() > 0.1


# --------------------------------------------------------------------------
# learn_structure_jit + single-dataset evaluation
# --------------------------------------------------------------------------

def test_learn_structure_jit_matches_host_pipeline():
    rng = np.random.default_rng(11)
    d, n = 12, 4_000
    edges = trees.random_tree(d, rng)
    w = rng.uniform(0.5, 0.85, size=d - 1)
    x = sampler.sample_tree_ggm(jax.random.key(4), n, d, edges, w)
    for strat in (Strategy("sign"), Strategy("persymbol", rate=4),
                  Strategy("original")):
        adj = CL.learn_structure_jit(x, strat)
        assert isinstance(adj, jax.Array) and adj.dtype == jnp.bool_
        est_host = CL.learn_structure(
            x, method=strat.method,
            rate=strat.rate if strat.method == "persymbol" else 1)
        assert trees.edges_canonical(CL.adjacency_to_edges(adj)) == \
            trees.edges_canonical(est_host)


def test_evaluate_strategies_scores_recovery():
    rng = np.random.default_rng(3)
    d, n = 10, 6_000
    edges = trees.random_tree(d, rng)
    w = rng.uniform(0.5, 0.85, size=d - 1)
    x = sampler.sample_tree_ggm(jax.random.key(9), n, d, edges, w)
    adj_true = jnp.asarray(trees.tree_adjacency(d, edges))
    out = evaluate_strategies(x, adj_true,
                              (Strategy("sign"), Strategy("original")))
    assert set(out) == {"sign", "original"}
    assert out["original"]["error"] == 0.0
    assert out["original"]["edit_distance"] == 0.0
    assert out["original"]["edge_f1"] == pytest.approx(1.0)


# --------------------------------------------------------------------------
# run_trials: the vmapped sweep engine
# --------------------------------------------------------------------------

def test_trial_plan_validation():
    with pytest.raises(ValueError):
        TrialPlan(d=10, ns=(100,), tree="loop")
    with pytest.raises(ValueError):
        TrialPlan(d=10, ns=(100,), tree="skeleton")
    with pytest.raises(ValueError):
        TrialPlan(d=1, ns=(100,))


def test_run_trials_shapes_and_telemetry():
    plan = TrialPlan(d=8, ns=(200, 800),
                     strategies=(Strategy("sign"), Strategy("original")),
                     reps=6)
    res = run_trials(plan)
    assert set(res.error_rate) == {"sign", "original"}
    assert all(len(v) == 2 for v in res.error_rate.values())
    # the WHOLE sweep performs exactly one host sync (the metric tensor)
    assert res.host_syncs == 1
    assert res.buckets == {200: 256, 800: 1024}  # pow2 default
    assert res.mesh_devices == 1
    assert res.compile_cache_size > 0
    assert res.trials_per_s > 0
    for errs in res.error_rate.values():
        assert all(0.0 <= e <= 1.0 for e in errs)
    # more data can't make the unquantized method catastrophically worse
    assert res.error_rate["original"][1] <= res.error_rate["original"][0] + 0.5


def test_run_trials_seconds_cover_the_host_tree_draws(monkeypatch):
    from repro.core import experiments

    def slow(*args, _draw=experiments._draw_tree):
        time.sleep(0.1)
        return _draw(*args)

    plan = TrialPlan(d=6, ns=(64,), strategies=(Strategy("sign"),), reps=4)
    run_trials(plan)  # compile outside the timed call
    monkeypatch.setattr(experiments, "_draw_tree", slow)
    fresh = dataclasses.replace(plan, seed0=plan.seed0 + 1)  # a draw miss
    t0 = time.perf_counter()
    res = run_trials(fresh)
    wall = time.perf_counter() - t0
    assert 4 * 0.1 <= res.seconds <= wall


def test_run_trials_deterministic():
    plan = TrialPlan(d=7, ns=(300,), strategies=(Strategy("sign"),), reps=5)
    r1, r2 = run_trials(plan), run_trials(plan)
    assert r1.error_rate == r2.error_rate
    assert r1.edit_distance == r2.edit_distance


def test_run_trials_no_implicit_host_transfers():
    """The sweep body must survive a disallow d2h transfer guard: only
    the engine's single explicit jax.device_get touches the host.
    (Hard assertion on accelerator backends; on CPU d2h reads are
    zero-copy and unguarded, so there this is a plain smoke.)"""
    plan = TrialPlan(d=6, ns=(150,),
                     strategies=(Strategy("sign"), Strategy("original")),
                     reps=4)
    run_trials(plan)  # cold: compiles outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        res = run_trials(plan)
    assert res.host_syncs == 1


def test_stacked_trees_match_reference_rng():
    """The engine's per-rep tree/weight draws equal GGMDataset's (same
    default_rng(seed0 + rep) consumption order)."""
    from repro.data import GGMDataset

    plan = TrialPlan(d=9, ns=(100,), reps=4, seed0=17,
                     rho_min=0.3, rho_max=0.8)
    parents, rhos, adj = stacked_trees(plan)
    assert trial_keys(plan).shape[0] == plan.reps
    for rep in range(plan.reps):
        ds = GGMDataset(d=9, rho_min=0.3, rho_max=0.8, seed=17 + rep)
        edges, w = ds.structure()
        parent, rho, perm = trees.topological_parents(9, edges, w)
        assert (np.asarray(parents[rep]) == parent).all()
        assert np.allclose(np.asarray(rhos[rep]), rho)
        adj_h = trees.tree_adjacency(9, edges)[np.ix_(perm, perm)]
        assert (np.asarray(adj[rep]) == adj_h).all()


def recovery_error_rate(
    d: int, n: int, method: str, rate: int, reps: int,
    tree: str = "random", rho_min: float = 0.4, rho_max: float = 0.9,
    seed0: int = 0,
) -> float:
    """Empirical Pr(T_hat != T) over ``reps`` independent (tree, data) draws.

    The per-trial reference loop: one Python iteration and one
    device->host round-trip per trial, through the public
    ``chow_liu.learn_structure``. ``run_trials`` replaces it; the loop is
    kept as the semantic reference the trial plane is checked against.
    Per-rep seeding (tree and weights from ``default_rng(seed0 + rep)``)
    matches ``experiments.stacked_trees``.
    """
    from repro.data import GGMDataset

    bad = 0
    for rep in range(reps):
        ds = GGMDataset(d=d, tree=tree, rho_min=rho_min, rho_max=rho_max,
                        seed=seed0 + rep)
        edges, _ = ds.structure()
        x = ds.sample(n, batch_seed=rep)
        est = CL.learn_structure(x, method=method, rate=max(rate, 1))
        bad += trees.tree_edit_distance(edges, est) > 0
    return bad / reps


def test_run_trials_matches_reference_loop_fig3_point():
    """run_trials reproduces a fig3 sweep point computed by the legacy
    per-trial host loop, within Monte-Carlo tolerance (satellite req)."""
    d, n, reps = 20, 500, 60
    plan = TrialPlan(d=d, ns=(n,), strategies=(Strategy("sign"),), reps=reps)
    dev = run_trials(plan).error_rate["sign"][0]
    host = recovery_error_rate(d, n, "sign", 1, reps)
    # same ground-truth trees (shared seeding), independent sampling
    # streams: binomial noise only. std <= sqrt(2 * 0.25 / 60) ~ 0.09.
    assert abs(dev - host) <= 0.25, (dev, host)


# --------------------------------------------------------------------------
# Shape bucketing: plan knobs, shape-stable sampler, masked weights, parity
# --------------------------------------------------------------------------

def test_bucket_resolution_and_validation():
    assert next_pow2(1) == 8 and next_pow2(8) == 8
    assert next_pow2(125) == 128 and next_pow2(1000) == 1024
    plan = TrialPlan(d=6, ns=(125, 250), strategies=(Strategy("sign"),))
    assert plan.buckets == {125: 128, 250: 256}
    exact = TrialPlan(d=6, ns=(125,), strategies=(Strategy("sign"),),
                      n_buckets=None)
    assert exact.bucket_for(125) == 125
    custom = TrialPlan(d=6, ns=(125, 250), strategies=(Strategy("sign"),),
                       n_buckets=(256,))
    assert custom.buckets == {125: 256, 250: 256}
    with pytest.raises(ValueError):  # buckets must cover max(ns)
        TrialPlan(d=6, ns=(300,), strategies=(Strategy("sign"),),
                  n_buckets=(256,))
    with pytest.raises(ValueError):
        TrialPlan(d=6, ns=(100,), strategies=(Strategy("sign"),),
                  n_buckets="pow3")


def test_row_sampler_prefix_is_shape_stable():
    """The bucketed sampler's first m rows equal the (m, d) draw
    bit-for-bit — the property that makes padded sweeps replayable."""
    _, _, parent, rho, _ = _random_tree_arrays(9, 4)
    P, R = jnp.asarray(parent), jnp.asarray(rho)
    key = jax.random.key(3)
    small = np.asarray(sampler.sample_tree_ggm_rows(key, 100, P, R))
    big = np.asarray(sampler.sample_tree_ggm_rows(key, 256, P, R))
    assert np.array_equal(big[:100], small)
    # batched form agrees with the per-trial form
    keys = trial_keys(TrialPlan(d=9, ns=(10,), reps=3))
    xb = np.asarray(sampler.sample_tree_ggm_rows_batch(
        keys, 64, jnp.stack([P] * 3), jnp.stack([R] * 3)))
    assert np.array_equal(
        xb[1], np.asarray(sampler.sample_tree_ggm_rows(keys[1], 64, P, R)))


def test_masked_batch_weights_match_unmasked():
    """strategy_weights_batch under a valid-length mask == the per-sample
    strategy_weights on the valid prefix: bit-equal off-diagonal for the
    integer-exact sign paths, rounding-tight for the float paths."""
    rng = np.random.default_rng(5)
    n, n_pad, d = 120, 256, 7
    x = jnp.asarray(rng.normal(size=(2, n, d)).astype(np.float32))
    xpad = jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)),
                   constant_values=99.0)  # poison the pad rows
    off = ~np.eye(d, dtype=bool)
    for strat in (Strategy("sign"), Strategy("sign", wire="packed"),
                  Strategy("persymbol", rate=3), Strategy("original")):
        ref = np.stack([np.asarray(
            estimators.strategy_weights(x[i], strat)) for i in range(2)])
        got = np.asarray(estimators.strategy_weights_batch(
            xpad, strat, n_valid=jnp.int32(n)))
        if strat.method == "sign":
            assert np.array_equal(got[:, off], ref[:, off]), strat.label
        else:
            np.testing.assert_allclose(
                got[:, off], ref[:, off], rtol=1e-5, atol=1e-5)


def test_run_trials_bucketing_parity_fig3_scale():
    """Satellite requirement: for every Fig.-3 strategy, bucketing on vs
    off yields IDENTICAL metrics on a fig3-scale plan (d=20, padded ns)."""
    kw = dict(d=20, ns=(125, 500), strategies=FIG3_STRATEGIES, reps=10)
    on = run_trials(TrialPlan(**kw))                   # pow2 buckets
    off = run_trials(TrialPlan(**kw, n_buckets=None))  # exact shapes
    assert on.buckets == {125: 128, 500: 512}
    assert off.buckets == {125: 125, 500: 500}
    for s in FIG3_STRATEGIES:
        assert on.error_rate[s.label] == off.error_rate[s.label], s.label
        assert on.edit_distance[s.label] == off.edit_distance[s.label], s.label
        assert on.edge_f1[s.label] == off.edge_f1[s.label], s.label


def test_compile_cache_helpers_and_plan_setup_cache():
    plan = TrialPlan(d=5, ns=(40,), strategies=(Strategy("sign"),), reps=3)
    run_trials(plan)
    assert compile_cache_size() > 0
    # per-plan host setup (trees + keys) is cached: same objects back
    assert stacked_trees(plan)[0] is stacked_trees(plan)[0]
    assert trial_keys(plan) is trial_keys(plan)
    released = clear_compile_caches()
    assert released >= 1
    assert compile_cache_size() == 0
    # engine still works from a cold cache
    res = run_trials(plan)
    assert res.host_syncs == 1


def test_run_trials_host_kruskal_matches_device():
    """The host-loop escape hatch (run_trials(mst='host_kruskal')) is
    metric-identical to the device Boruvka path on the current estimators
    (the rank-equivalence the hatch exists to outlive), still one host
    sync (a single stacked weights device_get)."""
    plan = TrialPlan(d=10, ns=(60, 250), strategies=FIG3_STRATEGIES[:3],
                     reps=6)
    dev = run_trials(plan)
    host = run_trials(plan, mst="host_kruskal")
    assert host.host_syncs == 1
    for s in plan.strategies:
        lab = s.label
        assert host.error_rate[lab] == dev.error_rate[lab], lab
        assert host.edit_distance[lab] == dev.edit_distance[lab], lab
        assert host.edge_f1[lab] == dev.edge_f1[lab], lab
    with pytest.raises(ValueError):
        run_trials(plan, mst="prim")
    with pytest.raises(ValueError):  # the hatch is single-process only
        import jax as _jax
        run_trials(plan, mst="host_kruskal",
                   mesh=_jax.make_mesh((1,), ("data",)))


def test_trial_result_comm_reports():
    """Every sweep carries honest per-strategy communication accounting:
    the paper's logical n*d*R next to the wire bytes of the (bucketed)
    payload the encode stage emits."""
    plan = TrialPlan(d=8, ns=(100,),
                     strategies=(Strategy("sign", wire="packed"),
                                 Strategy("persymbol", rate=4),
                                 Strategy("original")),
                     reps=4)
    res = run_trials(plan)
    comm = res.comm
    assert set(comm) == set(res.error_rate)
    n_pad = plan.bucket_for(100)  # 128
    assert comm["sign"][0].logical_bits == 100 * 8
    assert comm["sign"][0].wire_bytes == n_pad * 8 // 8     # 1 bit/sym
    assert comm["sign"][0].collectives == 0                 # no wire mesh
    assert comm["R4"][0].logical_bits == 4 * 100 * 8
    assert comm["R4"][0].wire_bytes == n_pad * 8            # byte per code
    assert comm["original"][0].wire_bytes == 4 * n_pad * 8  # f32 wire
    assert comm["original"][0].wire_bits == 8 * 4 * n_pad * 8
    assert comm["sign"][0].overhead == pytest.approx(
        n_pad / 100)  # padding is the only packed-wire overhead


# --------------------------------------------------------------------------
# Strategy plumbing through the other layers
# --------------------------------------------------------------------------

def test_strategy_weights_matches_method_estimators():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(256, 6)).astype(np.float32))
    from repro.core.quantizers import PerSymbolQuantizer, sign_codes

    w_sign = estimators.strategy_weights(x, Strategy("sign"))
    assert np.allclose(w_sign, estimators.sign_method_weights(sign_codes(x)))
    # packed wire == int8 wire (same statistic, different transport)
    w_packed = estimators.strategy_weights(x, Strategy("sign", wire="packed"))
    assert np.allclose(w_sign, w_packed, atol=1e-5)
    q = PerSymbolQuantizer(3)
    w_ps = estimators.strategy_weights(x, Strategy("persymbol", rate=3))
    codes = q.encode(x).astype(jnp.int8)
    assert np.allclose(
        w_ps, estimators.persymbol_code_weights(codes, q.centroids))
    w_orig = estimators.strategy_weights(x, Strategy("original"))
    assert np.allclose(w_orig, estimators.gaussian_weights(x))


def test_streaming_from_strategy_and_device_learn():
    rng = np.random.default_rng(2)
    d, n = 8, 2_000
    edges = trees.random_tree(d, rng)
    w = rng.uniform(0.5, 0.8, size=d - 1)
    x = sampler.sample_tree_ggm(jax.random.key(0), n, d, edges, w)
    sg = StreamingGram.from_strategy(d, Strategy("persymbol", rate=4))
    assert sg.method == "persymbol" and sg.rate == 4
    for lo in range(0, n, 500):
        sg.update(x[lo:lo + 500])
    adj = sg.learn_adjacency()
    assert isinstance(adj, jax.Array) and adj.dtype == jnp.bool_
    assert trees.edges_canonical(sg.learn_structure("boruvka")) == \
        trees.edges_canonical(sg.learn_structure("kruskal"))
    with pytest.raises(ValueError):
        sg.learn_structure("nope")


def test_streaming_batch_ingestion_matches_sequential():
    """update_codes_batch / update_packed_batch (one batched Gram launch
    for a stack of per-machine blocks) fold in exactly what the sequential
    per-block updates fold in."""
    from repro.core.quantizers import PerSymbolQuantizer, bitpack_signs

    rng = np.random.default_rng(7)
    d, n_b, m = 6, 64, 4
    x = rng.normal(size=(m, n_b, d)).astype(np.float32)

    # per-symbol codes
    q = PerSymbolQuantizer(3)
    codes = np.asarray(q.encode(jnp.asarray(x)))
    seq = StreamingGram(d=d, method="persymbol", rate=3)
    for i in range(m):
        seq.update_codes(jnp.asarray(codes[i]))
    bat = StreamingGram(d=d, method="persymbol", rate=3)
    bat.update_codes_batch(jnp.asarray(codes).astype(jnp.int8))
    assert bat.n == seq.n == m * n_b
    assert np.allclose(np.asarray(bat.gram), np.asarray(seq.gram), atol=1e-4)
    assert np.allclose(np.asarray(bat.weights()), np.asarray(seq.weights()),
                       atol=1e-5)

    # sign codes (int8 wire) and 1-bit packed payloads
    seq = StreamingGram(d=d, method="sign")
    bat = StreamingGram(d=d, method="sign")
    pk_seq = StreamingGram(d=d, method="sign")
    pk_bat = StreamingGram(d=d, method="sign")
    signs = (x >= 0).astype(np.int8)
    payloads = bitpack_signs(
        jnp.asarray(np.swapaxes(np.where(signs > 0, 1, -1), 1, 2)))
    for i in range(m):
        seq.update_codes(jnp.asarray(signs[i]))
        pk_seq.update_packed(payloads[i], n_b)
    bat.update_codes_batch(jnp.asarray(signs))
    pk_bat.update_packed_batch(payloads, n_b)
    # integer-exact paths: bit-equal accumulators
    assert np.array_equal(np.asarray(bat.gram), np.asarray(seq.gram))
    assert np.array_equal(np.asarray(pk_bat.gram), np.asarray(pk_seq.gram))
    assert np.array_equal(np.asarray(pk_bat.gram), np.asarray(bat.gram))
    assert pk_bat.n == bat.n == m * n_b
    with pytest.raises(ValueError):
        StreamingGram(d=d, method="original").update_codes_batch(
            jnp.asarray(signs))


def test_mc_engines_run_and_bound():
    # crossover rate in [0, 1], decreasing in n for a well-separated pair
    lo = mc_sign_crossover(160, 0.9, 0.1, reps=2000)
    hi = mc_sign_crossover(10, 0.9, 0.1, reps=2000)
    assert 0.0 <= lo <= hi <= 1.0
    # quantizer error shrinks with rate
    e1 = mc_persymbol_corr_error(500, 0.5, 1, reps=200)
    e4 = mc_persymbol_corr_error(500, 0.5, 4, reps=200)
    assert e4 < e1


# --------------------------------------------------------------------------
# Sparse trial plane (the §7 extension: glasso over quantized data)
# --------------------------------------------------------------------------

SPARSE_STRATS = (Strategy("sign", structure="sparse", lam=0.08),
                 Strategy("persymbol", rate=4, structure="sparse", lam=0.06))


def _sparse_plan(**kw):
    base = dict(d=10, ns=(300, 900), tree="sparse", density=0.25,
                strategies=SPARSE_STRATS, reps=6, glasso_steps=150)
    base.update(kw)
    return TrialPlan(**base)


def test_sparse_strategy_axis():
    s = Strategy("persymbol", rate=4, structure="sparse", lam=0.06)
    assert s.label == "R4+glasso0.06"
    assert Strategy("sign", structure="sparse", lam=0.1).label \
        == "sign+glasso0.1"
    # lam is a sparse-only knob: a tree strategy with lam set is almost
    # certainly a forgotten structure="sparse" — fail loudly
    with pytest.raises(ValueError):
        Strategy("sign", lam=0.5)
    assert Strategy("sign", lam=0.0).lam == 0.0
    with pytest.raises(ValueError):
        Strategy("sign", structure="sparse")  # lam missing
    with pytest.raises(ValueError):
        Strategy("sign", structure="lattice", lam=0.1)
    # hashable, distinct per lam (lambda-path sweeps key result columns)
    assert len({Strategy("sign", structure="sparse", lam=l)
                for l in (0.05, 0.1, 0.05)}) == 2


def test_sparse_plan_validation():
    # structure homogeneity: tree + sparse strategies cannot share a plan
    with pytest.raises(ValueError):
        TrialPlan(d=10, ns=(100,), tree="sparse",
                  strategies=(Strategy("sign"),) + SPARSE_STRATS[:1])
    # tree kind and strategy structure must agree, both ways
    with pytest.raises(ValueError):
        TrialPlan(d=10, ns=(100,), tree="random", strategies=SPARSE_STRATS)
    with pytest.raises(ValueError):
        TrialPlan(d=10, ns=(100,), tree="sparse",
                  strategies=(Strategy("sign"),))
    with pytest.raises(ValueError):
        _sparse_plan(density=0.0)
    assert _sparse_plan().structure == "sparse"
    assert TrialPlan(d=10, ns=(100,)).structure == "tree"
    # the tree-only host-Kruskal hatch rejects sparse plans
    with pytest.raises(ValueError):
        run_trials(_sparse_plan(), mst="host_kruskal")


def test_sparse_run_trials_telemetry_and_one_sync():
    plan = _sparse_plan()
    run_trials(plan)  # cold: compiles
    with jax.transfer_guard_device_to_host("disallow"):
        res = run_trials(plan)
    assert res.host_syncs == 1
    labels = [s.label for s in SPARSE_STRATS]
    for table in (res.error_rate, res.edit_distance, res.edge_f1,
                  res.precision, res.recall):
        assert sorted(table) == sorted(labels)
        assert all(len(v) == 2 for v in table.values())
    for lab in labels:
        assert all(0.0 <= v <= 1.0 for v in res.edge_f1[lab])
        assert all(0.0 <= v <= 1.0 for v in res.precision[lab])
        assert all(0.0 <= v <= 1.0 for v in res.recall[lab])
        # micro-F1 is exactly the harmonic combination of the P/R channels
        for f1, p, r in zip(res.edge_f1[lab], res.precision[lab],
                            res.recall[lab]):
            assert abs(f1 - 2 * p * r / max(p + r, 1e-9)) < 1e-5
        assert res.comm[lab][0].logical_bits > 0
    # support recovery improves with data for the 4-bit method (paper §7)
    assert res.edge_f1[labels[1]][1] >= res.edge_f1[labels[1]][0] - 0.05


def test_sparse_run_trials_matches_reference_loop():
    """One-launch sparse engine == the per-trial public-API chain
    (sample_ggm_rows -> strategy_corr -> glasso -> partial-corr support),
    metric for metric."""
    from repro.core import glasso
    from repro.core.experiments import sparse_ground_truth, trial_keys

    plan = _sparse_plan(n_buckets=None)
    res = run_trials(plan)
    chols, adj_true = sparse_ground_truth(plan)
    keys = trial_keys(plan)
    for s in SPARSE_STRATS:
        lab = s.label
        for i_n, n in enumerate(plan.ns):
            errs, hams, sh, ne, nt = [], [], 0, 0, 0
            for rep in range(plan.reps):
                x = sampler.sample_ggm_rows(keys[rep], n, chols[rep])
                corr = estimators.strategy_corr(x, s)
                theta = glasso.glasso_batch(
                    corr[None], s.lam, n_steps=plan.glasso_steps)[0]
                est = glasso.support(theta, plan.glasso_tol)
                true = np.asarray(adj_true[rep])
                errs.append((est != true).any())
                hams.append((est != true).sum() // 2)
                sh += (est & true).sum() // 2
                ne += est.sum() // 2
                nt += true.sum() // 2
            assert abs(res.error_rate[lab][i_n] - np.mean(errs)) < 1e-6
            assert abs(res.edit_distance[lab][i_n] - np.mean(hams)) < 1e-6
            assert abs(res.precision[lab][i_n] - sh / max(ne, 1)) < 1e-5
            assert abs(res.recall[lab][i_n] - sh / max(nt, 1)) < 1e-5
            assert abs(res.edge_f1[lab][i_n]
                       - 2 * sh / max(ne + nt, 1)) < 1e-5


def test_sparse_run_trials_bucketing_parity():
    """Bucketed sparse sweeps recover identical metrics: the row-keyed
    generic sampler makes padded draws bit-equal on the valid prefix and
    the sign Gram is integer-exact through the mask."""
    exact = run_trials(_sparse_plan(n_buckets=None))
    bucketed = run_trials(_sparse_plan(n_buckets="pow2"))
    assert bucketed.buckets == {300: 512, 900: 1024}
    for lab in exact.error_rate:
        assert bucketed.error_rate[lab] == exact.error_rate[lab], lab
        assert bucketed.edit_distance[lab] == exact.edit_distance[lab], lab
        assert bucketed.edge_f1[lab] == exact.edge_f1[lab], lab


def test_sparse_ground_truth_matches_reference_rng():
    """Trial rep's ground truth == glasso.random_sparse_precision under
    default_rng(seed0 + rep), Cholesky-factored — the same per-rep rng
    convention as the tree plane."""
    from repro.core import glasso
    from repro.core.experiments import sparse_ground_truth

    plan = _sparse_plan(seed0=7)
    chols, adj_true = sparse_ground_truth(plan)
    for rep in (0, plan.reps - 1):
        rng = np.random.default_rng(7 + rep)
        theta = glasso.random_sparse_precision(
            plan.d, plan.density, rng,
            strength=(plan.rho_min, plan.rho_max))
        a = np.abs(theta) > 1e-8
        np.fill_diagonal(a, False)
        assert (np.asarray(adj_true[rep]) == a).all()
        cov = np.linalg.inv(theta)
        np.testing.assert_allclose(
            np.asarray(chols[rep]), np.linalg.cholesky(cov).astype(
                np.float32), atol=1e-6)


# The paper's §7 claims on the sparse trial plane, at d=16, lam=0.06 and
# n up to 2000: one sweep with fixed penalties and one with the path
# engine's EBIC selection, both checked at the largest n.
_CLAIM_STRATS = tuple(
    Strategy(m, rate=r, structure="sparse", lam=0.06)
    for m, r in (("sign", 1), ("persymbol", 4), ("original", 1)))


@pytest.fixture(scope="module")
def sparse_claim_sweeps():
    from repro.core.path import PathPlan

    plan = TrialPlan(d=16, ns=(250, 1000, 2000), tree="sparse",
                     density=0.18, strategies=_CLAIM_STRATS, reps=32,
                     rho_min=0.25, rho_max=0.45, glasso_steps=300)
    path_plan = dataclasses.replace(
        plan, path=PathPlan(n_lams=6, lam_min_ratio=0.08))
    with jax.transfer_guard_device_to_host("disallow"):
        return run_trials(plan), run_trials(path_plan)


_SPARSE_CLAIMS = {
    # 4-bit per-symbol glasso recovers about what unquantized glasso does
    "r4_close_to_original": lambda f1, path: f1["R4"][-1]
    >= f1["original"][-1] - 0.08,
    "monotone_in_rate": lambda f1, path: f1["sign"][-1]
    <= f1["R4"][-1] + 0.05,
    "f1_improves_with_n": lambda f1, path: f1["R4"][-1]
    >= f1["R4"][0] - 0.05,
    "original_good": lambda f1, path: f1["original"][-1] > 0.85,
    # the EBIC-selected support competes with the hand-tuned penalty
    "path_selected_competitive": lambda f1, path: path["original"][-1]
    >= f1["original"][-1] - 0.10,
}


@pytest.mark.parametrize("claim", [*_SPARSE_CLAIMS, "one_sync_per_sweep"])
def test_sparse_plane_paper_claims(sparse_claim_sweeps, claim):
    fixed, path = sparse_claim_sweeps
    if claim == "one_sync_per_sweep":
        assert fixed.host_syncs == path.host_syncs == 1
        return
    f1, f1_path = ({m: r.edge_f1[s.label]
                    for m, s in zip(("sign", "R4", "original"),
                                    _CLAIM_STRATS)}
                   for r in (fixed, path))
    assert _SPARSE_CLAIMS[claim](f1, f1_path), (f1, f1_path)


def test_tree_results_fill_precision_recall():
    """Tree plans populate the new precision/recall channels with the
    spanning-tree identity precision == recall == F1 (est == true == d-1),
    leaving every pre-existing metric unchanged."""
    plan = TrialPlan(d=8, ns=(400,),
                     strategies=(Strategy("sign"), Strategy("original")),
                     reps=5)
    res = run_trials(plan)
    assert res.precision == res.edge_f1
    assert res.recall == res.edge_f1


def test_edge_counts_channels():
    est = jnp.zeros((4, 4), bool).at[0, 1].set(True).at[1, 0].set(True) \
        .at[2, 3].set(True).at[3, 2].set(True)
    true = jnp.zeros((4, 4), bool).at[0, 1].set(True).at[1, 0].set(True) \
        .at[1, 2].set(True).at[2, 1].set(True)
    shared, n_est, n_true = trees.edge_counts(est, true)
    assert (int(shared), int(n_est), int(n_true)) == (1, 2, 2)
    # broadcasting over leading axes (the metric stage's (S, r) batch)
    shared, n_est, n_true = trees.edge_counts(est[None, None], true[None])
    assert shared.shape == n_est.shape == n_true.shape == (1, 1)


def test_r1_bucketing_parity_at_32x_padding():
    """Regression for the full-mode trials bench flake: R1 metrics under
    EXTREME (32x) shape bucketing must equal the exact-shape run bit for
    bit — the R1 code Gram now rides the integer sign contraction, so
    padded shapes cannot reorder its reduction and flip MWST near-ties."""
    kw = dict(d=20, ns=(125,),
              strategies=(Strategy("persymbol", rate=1),), reps=24)
    exact = run_trials(TrialPlan(**kw, n_buckets=None))
    padded = run_trials(TrialPlan(**kw, n_buckets=(4096,)))
    assert padded.buckets == {125: 4096}
    assert exact.error_rate["R1"] == padded.error_rate["R1"]
    assert exact.edit_distance["R1"] == padded.edit_distance["R1"]
    assert exact.edge_f1["R1"] == padded.edge_f1["R1"]


def test_r1_weights_stage_bitwise_stable_under_bucketing():
    """The property UNDER the metric parity above, asserted where the
    flake actually lived: the jitted weights stage must produce
    bit-identical R1 weight tensors at the exact shape and under 8x
    padding. This is only true when the engine's integer-exact rate-1
    dispatch engages INSIDE the trace — the quantizer codebook handed to
    the Gram must be concrete (``centroids_np``), because a
    traced-codebook fallback to the f32 centroid decode reintroduces
    reduction-order drift (the n=500 near-tie the full trials bench
    caught)."""
    import jax.numpy as jnp

    from repro.core.experiments import _weights_stage, stacked_trees, trial_keys
    from repro.core.gram import GramEngine

    strategies = (Strategy("persymbol", rate=1),)
    plan = TrialPlan(d=20, ns=(500,), strategies=strategies, reps=60,
                     n_buckets=None)
    keys = trial_keys(plan)
    parents, rhos, _ = stacked_trees(plan)
    eng = plan.budget_engine(GramEngine())
    n_valid = jnp.asarray(500)
    w_exact = np.asarray(
        _weights_stage(strategies, 500, eng, None)(
            keys, parents, rhos, n_valid))
    w_padded = np.asarray(
        _weights_stage(strategies, 4096, eng, None)(
            keys, parents, rhos, n_valid))
    assert np.array_equal(w_exact, w_padded)
