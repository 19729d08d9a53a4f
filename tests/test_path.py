"""Regularization-path engine: warm-started grid scan, early exit,
on-device EBIC/StARS selection, and the trial/wire-plane wiring."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as core
from repro.core import glasso, sampler
from repro.core.path import (PathPlan, ebic_scores, glasso_path_batch,
                             glasso_path_select, path_lambdas, select_ebic,
                             select_stars, stars_instability)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_devices(script: str, n_devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="module")
def sparse_problem():
    """A seeded recovery problem: (corr statistic, true adjacency, n)."""
    rng = np.random.default_rng(3)
    d = 10
    theta = glasso.random_sparse_precision(d, density=0.25, rng=rng)
    cov = np.linalg.inv(theta)
    n = 6000
    x = sampler.sample_ggm(jax.random.key(3), n, cov)
    S = np.corrcoef(np.asarray(x), rowvar=False).astype(np.float32)
    true_adj = np.abs(theta) > 1e-8
    np.fill_diagonal(true_adj, False)
    return jnp.asarray(S), true_adj, n


# ---------------------------------------------------------------------------
# PathPlan validation
# ---------------------------------------------------------------------------

def test_path_plan_validation():
    PathPlan()  # defaults valid
    PathPlan(lams=(0.5, 0.1, 0.02))
    with pytest.raises(ValueError):
        PathPlan(lams=(0.5,))                # too short
    with pytest.raises(ValueError):
        PathPlan(lams=(0.1, 0.5))            # increasing
    with pytest.raises(ValueError):
        PathPlan(lams=(0.5, -0.1))           # non-positive
    with pytest.raises(ValueError):
        PathPlan(n_lams=1)
    with pytest.raises(ValueError):
        PathPlan(lam_min_ratio=1.5)
    with pytest.raises(ValueError):
        PathPlan(select="aic")
    with pytest.raises(ValueError):
        PathPlan(ebic_gamma=-1.0)
    with pytest.raises(ValueError):
        PathPlan(stars_beta=0.0)
    with pytest.raises(ValueError):
        PathPlan(conv_tol=-1e-3)
    assert PathPlan(lams=(0.5, 0.1)).k == 2
    assert PathPlan(n_lams=7).k == 7
    assert hash(PathPlan()) == hash(PathPlan())  # hashable plan object


def test_path_lambdas_derived_grid():
    S = jnp.asarray(np.array([[1.0, 0.4], [0.4, 1.0]], np.float32))
    plan = PathPlan(n_lams=5, lam_min_ratio=0.1)
    grid = np.asarray(path_lambdas(plan, S))
    assert grid.shape == (5,)
    assert np.isclose(grid[0], 0.4)
    assert np.isclose(grid[-1], 0.04)
    assert (np.diff(grid) < 0).all()
    # explicit grids broadcast over the batch
    plan2 = PathPlan(lams=(0.3, 0.1))
    got = np.asarray(path_lambdas(plan2, jnp.stack([S, S])))
    assert got.shape == (2, 2) and np.allclose(got, [0.3, 0.1])
    # all-zero pad statistic still yields a valid positive decreasing grid
    z = np.asarray(path_lambdas(plan, jnp.eye(2)))
    assert (z > 0).all() and (np.diff(z) < 0).all()


# ---------------------------------------------------------------------------
# warm path vs cold per-lam parity
# ---------------------------------------------------------------------------

def test_warm_path_matches_cold_per_lam_solves(sparse_problem):
    """Satellite gate: each lam's warm-started iterate agrees with a cold
    full-budget solve at that penalty (within tol), and the SELECTED
    support is exactly the cold sweep's EBIC pick."""
    S, true_adj, n = sparse_problem
    plan = PathPlan(n_lams=6, lam_min_ratio=0.05, conv_tol=0.0)
    lams = path_lambdas(plan, S)
    solve = glasso_path_batch(S[None], lams, n_steps=400, conv_tol=0.0,
                              keep_thetas=True)
    cold_scores = []
    for i, lam in enumerate(np.asarray(lams)):
        cold = glasso.glasso(S, float(lam), n_steps=400)
        warm = solve.thetas[i, 0]
        assert float(jnp.max(jnp.abs(cold - warm))) < 5e-3, i
        # support agreement per lam at the default tol
        assert (np.asarray(glasso.support(cold))
                == np.asarray(solve.support[i, 0])).all(), i
        d = S.shape[0]
        off = ~np.eye(d, dtype=bool)
        e = int(np.asarray(glasso.support(cold)).sum()) // 2
        sign, logdet = np.linalg.slogdet(np.asarray(cold))
        tr = float(np.sum(np.asarray(S) * np.asarray(cold)))
        cold_scores.append(-n * (logdet - tr)
                           + e * (np.log(n) + 2.0 * np.log(d)))
    # selection parity: device EBIC pick == host pick over cold solves
    theta_sel, idx, _ = glasso_path_select(S, plan, n, n_steps=400)
    assert int(idx) == int(np.argmin(cold_scores))
    f1_true = 2 * (np.asarray(glasso.support(theta_sel)) & true_adj).sum()
    denom = np.asarray(glasso.support(theta_sel)).sum() + true_adj.sum()
    assert f1_true / max(denom, 1) > 0.8


def test_early_exit_never_changes_converged_iterates(sparse_problem):
    """Satellite gate: convergence freezes the carry, so a converged lane
    is BIT-IDENTICAL under a forced much larger step budget."""
    S, _, _ = sparse_problem
    plan = PathPlan(n_lams=5, lam_min_ratio=0.08)
    lams = path_lambdas(plan, S)
    a = glasso_path_batch(S[None], lams, n_steps=200, conv_tol=1e-5,
                          keep_thetas=True)
    b = glasso_path_batch(S[None], lams, n_steps=800, conv_tol=1e-5,
                          keep_thetas=True)
    conv = np.asarray(a.iters[:, 0]) < 200  # lanes that exited early
    assert conv.any(), "no lane converged — tolerance/budget mismatch"
    for i in np.flatnonzero(conv):
        assert (np.asarray(a.thetas[i]) == np.asarray(b.thetas[i])).all(), i
        assert int(a.iters[i, 0]) == int(b.iters[i, 0])
    # telemetry: iteration counts are per lam and within budget
    assert (np.asarray(a.iters) <= 200).all()
    assert (np.asarray(a.iters) >= 1).all()


def test_warm_start_saves_iterations(sparse_problem):
    """The point of the engine: warm-started later lams converge in far
    fewer steps than the first (cold) lam's budget."""
    S, _, _ = sparse_problem
    plan = PathPlan(n_lams=6, lam_min_ratio=0.05)
    lams = path_lambdas(plan, S)
    solve = glasso_path_batch(S[None], lams, n_steps=400, conv_tol=3e-4)
    iters = np.asarray(solve.iters[:, 0])
    assert iters.sum() < 6 * 400 * 0.5, iters  # >2x under the cold budget


# ---------------------------------------------------------------------------
# EBIC / StARS vs numpy host references
# ---------------------------------------------------------------------------

def test_ebic_scores_match_numpy_reference(sparse_problem):
    S, _, n = sparse_problem
    d = S.shape[0]
    plan = PathPlan(n_lams=6, lam_min_ratio=0.05)
    lams = path_lambdas(plan, S)
    solve = glasso_path_batch(S[None], lams, n_steps=300, conv_tol=0.0,
                              keep_thetas=True)
    gamma = 0.5
    dev = np.asarray(ebic_scores(solve.logdet, solve.tr_s_theta,
                                 solve.edges, n, d, gamma))
    for i in range(plan.k):
        th = np.asarray(solve.thetas[i, 0], np.float64)
        sign, logdet = np.linalg.slogdet(th)
        tr = float((np.asarray(S, np.float64) * th).sum())
        e = int(np.asarray(solve.edges[i, 0]))
        ref = -n * (logdet - tr) + e * (np.log(n) + 4 * gamma * np.log(d))
        assert abs(dev[i, 0] - ref) <= 5e-4 * abs(ref) + 0.5, (i, dev[i, 0], ref)
    idx = int(select_ebic(jnp.asarray(dev))[0])
    assert idx == int(np.argmin(dev[:, 0]))


def test_stars_matches_numpy_reference():
    """Device StARS (integer-exact disagreement counts + cummax
    monotonization) against a straightforward numpy implementation."""
    rng = np.random.default_rng(7)
    K, B, d = 5, 12, 8
    sup = rng.random((K, B, d, d)) < np.linspace(0.05, 0.6, K)[:, None, None, None]
    sup = sup | sup.transpose(0, 1, 3, 2)
    idx = np.arange(d)
    sup[:, :, idx, idx] = False
    xi_dev = np.asarray(stars_instability(jnp.asarray(sup)))
    # numpy reference: xi = mean over edges of 2*phi*(1-phi)
    phi = sup.mean(axis=1)
    pairs = d * (d - 1) / 2
    triu = np.triu_indices(d, 1)
    xi_ref = np.array([(2 * phi[k] * (1 - phi[k]))[triu].sum() / pairs
                       for k in range(K)])
    assert np.allclose(xi_dev, xi_ref, atol=1e-6), (xi_dev, xi_ref)
    for beta in (0.05, 0.2, 0.5):
        mono = np.maximum.accumulate(xi_ref)
        ok = np.flatnonzero(mono <= beta)
        ref_idx = int(ok[-1]) if ok.size else 0
        assert int(select_stars(jnp.asarray(xi_dev, jnp.float32), beta)) \
            == ref_idx, beta


def test_stars_selection_is_integer_exact():
    """The disagreement statistic is an integer ratio — two different
    orderings of the same supports give bitwise-equal instability."""
    rng = np.random.default_rng(1)
    K, B, d = 4, 16, 6
    sup = rng.random((K, B, d, d)) < 0.3
    sup = sup | sup.transpose(0, 1, 3, 2)
    idx = np.arange(d)
    sup[:, :, idx, idx] = False
    xi1 = np.asarray(stars_instability(jnp.asarray(sup)))
    perm = rng.permutation(B)
    xi2 = np.asarray(stars_instability(jnp.asarray(sup[:, perm])))
    assert (xi1 == xi2).all()


# ---------------------------------------------------------------------------
# batching / chunk streaming / pad short-circuit
# ---------------------------------------------------------------------------

def test_path_batch_chunk_parity(sparse_problem):
    """Chunked slab streaming is bit-identical to the monolithic vmap on
    every PathSolve channel (real slots never observe the pad)."""
    S, _, _ = sparse_problem
    rng = np.random.default_rng(0)
    batch = jnp.stack([S + 0.0, S * 0.95 + 0.05 * jnp.eye(S.shape[0]),
                       jnp.asarray(np.corrcoef(
                           rng.normal(size=(500, S.shape[0])),
                           rowvar=False).astype(np.float32))])
    plan = PathPlan(n_lams=4, lam_min_ratio=0.1)
    lams = path_lambdas(plan, batch)
    mono = glasso_path_batch(batch, lams, n_steps=120)
    chk = glasso_path_batch(batch, lams, n_steps=120, chunk=2)
    for a, b in zip(mono[:-1], chk[:-1]):  # thetas are None in both
        assert (np.asarray(a) == np.asarray(b)).all()


def test_glasso_batch_pad_short_circuit(sparse_problem):
    """Satellite gate: pow2 padding burns no solver iterations — an
    inactive lane exits its while-loop at step 0 — and real slots are
    bit-identical with and without the mask."""
    S, _, _ = sparse_problem
    batch = jnp.stack([S, 0.9 * S + 0.1 * jnp.eye(S.shape[0])])
    # real slots bit-identical across chunk sizes that force padding
    mono = glasso.glasso_batch(batch, 0.08, n_steps=150)
    for chunk in (2, 4, 8):
        got = glasso.glasso_batch(batch, 0.08, n_steps=150, chunk=chunk)
        assert (np.asarray(got) == np.asarray(mono)).all(), chunk
    # the mask machinery itself: an inactive lane spends zero iterations
    theta0, w0, v0, eta0, obj0 = glasso._carry_init(
        S, jnp.float32(0.08), 0.9, 1e-4)
    _, _, _, iters = glasso._glasso_run(
        theta0, w0, v0, eta0, obj0, S, jnp.float32(0.08), 100, 1e-4,
        0.0, jnp.asarray(False))
    assert int(iters) == 0
    _, _, _, iters_live = glasso._glasso_run(
        theta0, w0, v0, eta0, obj0, S, jnp.float32(0.08), 100, 1e-4,
        0.0, jnp.asarray(True))
    assert int(iters_live) == 100


def test_glasso_conv_tol_zero_matches_legacy(sparse_problem):
    """conv_tol=0.0 (the default) runs the full budget — same contract as
    the pre-path fori_loop solver."""
    S, _, _ = sparse_problem
    a = glasso.glasso(S, 0.08, n_steps=120)
    b = glasso.glasso(S, 0.08, n_steps=120, conv_tol=0.0)
    assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# learn_sparse_structure lam="path"
# ---------------------------------------------------------------------------

def test_learn_sparse_structure_path():
    rng = np.random.default_rng(5)
    d = 12
    theta = glasso.random_sparse_precision(d, density=0.2, rng=rng)
    cov = np.linalg.inv(theta)
    x = sampler.sample_ggm(jax.random.key(5), 30_000, cov)
    true_adj = np.abs(theta) > 1e-8
    np.fill_diagonal(true_adj, False)
    est = glasso.learn_sparse_structure(x, lam="path", tol=5e-3)
    tp = (est & true_adj).sum()
    f1 = 2 * tp / max(est.sum() + true_adj.sum(), 1)
    assert f1 > 0.8, f1
    # a caller-declared plan routes the same way
    est2 = glasso.learn_sparse_structure(
        x, lam=PathPlan(n_lams=6, lam_min_ratio=0.05), tol=5e-3)
    assert est2.shape == (d, d)
    with pytest.raises(ValueError):
        glasso.learn_sparse_structure(x, lam="grid")
    with pytest.raises(ValueError):
        glasso.learn_sparse_structure(x, lam=PathPlan(select="stars"))
    with pytest.raises(ValueError):
        glasso.learn_sparse_structure(x, lam=-0.1)


# ---------------------------------------------------------------------------
# trial plane: TrialPlan(path=...)
# ---------------------------------------------------------------------------

def test_trial_plan_path_validation():
    from repro.core.experiments import TrialPlan
    from repro.core.strategy import Strategy
    with pytest.raises(TypeError):
        TrialPlan(d=8, ns=(64,), strategies=(Strategy("sign"),),
                  path=(0.5, 0.1))
    with pytest.raises(ValueError):
        TrialPlan(d=8, ns=(64,), strategies=(Strategy("sign"),),
                  path=PathPlan())


def test_trial_plane_path_mode_one_sync():
    """A path-mode sparse sweep keeps the one-host-sync contract, scores
    the SELECTED support, and reports full-grid telemetry."""
    from repro.core.experiments import TrialPlan, run_trials
    from repro.core.strategy import Strategy
    strat = Strategy("sign", structure="sparse", lam=0.08)
    plan = TrialPlan(d=10, ns=(200, 800), tree="sparse", density=0.2,
                     strategies=(strat,), reps=8, glasso_steps=150,
                     path=PathPlan(n_lams=5, lam_min_ratio=0.08))
    with jax.transfer_guard_device_to_host("disallow"):
        res = run_trials(plan)
    assert res.host_syncs == 1
    lab = strat.label
    assert len(res.edge_f1[lab]) == 2
    assert res.path is not None and res.path["select"] == "ebic"
    assert res.path["k"] == 5
    for key in ("lams", "error_rate", "edge_f1", "iters", "selected_hist"):
        curves = res.path[key][lab]
        assert len(curves) == 2 and all(len(c) == 5 for c in curves), key
    # selection histogram sums to reps per n; iters within budget
    for row in res.path["selected_hist"][lab]:
        assert np.isclose(sum(row), plan.reps)
    for row in res.path["iters"][lab]:
        assert all(0 < v <= plan.glasso_steps for v in row)
    # more data -> recovery does not degrade
    assert res.edge_f1[lab][1] >= res.edge_f1[lab][0] - 0.05


def test_trial_plane_path_stars_selection():
    from repro.core.experiments import TrialPlan, run_trials
    from repro.core.strategy import Strategy
    strat = Strategy("sign", structure="sparse", lam=0.08)
    plan = TrialPlan(d=10, ns=(400,), tree="sparse", density=0.2,
                     strategies=(strat,), reps=8, glasso_steps=120,
                     path=PathPlan(n_lams=5, lam_min_ratio=0.1,
                                   select="stars", stars_beta=0.2))
    res = run_trials(plan)
    assert res.host_syncs == 1
    hist = np.asarray(res.path["selected_hist"][strat.label][0])
    # StARS picks ONE index per strategy/n: the histogram is a point mass
    assert np.isclose(hist.sum(), plan.reps)
    assert np.isclose(hist.max(), plan.reps)


def test_trial_plane_path_tiny_budget_metric_identity():
    """Satellite gate: a tiny memory budget (forcing chunked slab
    streaming through the path solver) reproduces the unconstrained
    sweep's metrics exactly."""
    from repro.core.experiments import TrialPlan, run_trials
    from repro.core.strategy import Strategy
    strat = Strategy("sign", structure="sparse", lam=0.08)
    kw = dict(d=10, ns=(200,), tree="sparse", density=0.2,
              strategies=(strat,), reps=8, glasso_steps=120,
              path=PathPlan(n_lams=4, lam_min_ratio=0.1))
    ref = run_trials(TrialPlan(**kw))
    tiny = run_trials(TrialPlan(**kw, memory_budget_bytes=1 << 16))
    assert tiny.tiling["metrics_chunk"] is not None
    lab = strat.label
    assert tiny.error_rate[lab] == ref.error_rate[lab]
    assert tiny.edge_f1[lab] == ref.edge_f1[lab]
    assert tiny.path["iters"][lab] == ref.path["iters"][lab]
    assert tiny.path["selected_hist"][lab] == ref.path["selected_hist"][lab]


# ---------------------------------------------------------------------------
# wire plane: distributed path mode (subprocess mesh parity)
# ---------------------------------------------------------------------------

def test_distributed_path_mesh_parity():
    """ACCEPTANCE GATE: the wire runtime's path mode — shard_map to the
    corr statistic, fused warm-started path + EBIC selection on top — is
    BIT-IDENTICAL on 1 vs 8 forced host devices (sign grams are
    integer-exact), for both compute placements."""
    run_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import PathPlan, glasso
        from repro.core.distributed import (distributed_learn_structure,
                                            distributed_weights)
        from repro.core.strategy import Strategy
        rng = np.random.default_rng(2)
        d = 8
        theta = glasso.random_sparse_precision(d, density=0.25, rng=rng)
        cov = np.linalg.inv(theta)
        L = np.linalg.cholesky(cov)
        x = jnp.asarray((rng.normal(size=(1024, d)) @ L.T)
                        .astype(np.float32))
        plan = PathPlan(n_lams=6, lam_min_ratio=0.05)
        mesh1 = jax.make_mesh((1, 1), ('data', 'model'))
        mesh8 = jax.make_mesh((2, 4), ('data', 'model'))
        for placement in ('replicated', 'rowblock'):
            strat = Strategy('sign', structure='sparse', lam=0.1,
                             placement=placement)
            w1 = np.asarray(distributed_weights(x, mesh1, strategy=strat,
                                                path=plan))
            w8 = np.asarray(distributed_weights(x, mesh8, strategy=strat,
                                                path=plan))
            assert (w1 == w8).all(), placement
            e1 = distributed_learn_structure(x, mesh1, strategy=strat,
                                             path=plan)
            e8 = distributed_learn_structure(x, mesh8, strategy=strat,
                                             path=plan)
            assert e1 == e8, placement
        # tree strategies have no penalty to select
        try:
            distributed_weights(x, mesh8, strategy=Strategy('sign'),
                                path=plan)
        except ValueError:
            pass
        else:
            raise AssertionError('tree + path must raise')
        print('distributed path parity OK')
    """)


def test_sparse_wire_trial_plane_path_parity():
    """Mesh 1-vs-8 parity for a PATH sweep through the trial plane: the
    shard_map still ends at the corr statistic, so selection metrics are
    bit-identical across meshes, one host sync per sweep."""
    run_devices("""
        import numpy as np, jax
        from repro.core import PathPlan
        from repro.core.experiments import TrialPlan, run_trials
        from repro.core.strategy import Strategy
        from repro.launch.mesh import make_trial_mesh
        strat = Strategy('sign', structure='sparse', lam=0.08)
        plan = TrialPlan(d=12, ns=(200, 800), tree='sparse', density=0.2,
                         strategies=(strat,), reps=8, glasso_steps=120,
                         path=PathPlan(n_lams=5, lam_min_ratio=0.08))
        ref = run_trials(plan)
        r24 = run_trials(plan, mesh=make_trial_mesh(2, model=4))
        assert r24.mesh_devices == 8 and r24.host_syncs == 1
        lab = strat.label
        assert r24.error_rate[lab] == ref.error_rate[lab]
        assert r24.edge_f1[lab] == ref.edge_f1[lab]
        assert r24.precision[lab] == ref.precision[lab]
        assert r24.recall[lab] == ref.recall[lab]
        assert r24.path['iters'][lab] == ref.path['iters'][lab]
        assert r24.path['selected_hist'][lab] == \
            ref.path['selected_hist'][lab]
        assert r24.path['edge_f1'][lab] == ref.path['edge_f1'][lab]
        print('sparse path trial plane parity OK')
    """)


# ---------------------------------------------------------------------------
# early exit against the full-budget oracle on a seeded problem batch
# ---------------------------------------------------------------------------

_ORACLE_D, _ORACLE_N, _ORACLE_STEPS = 16, 400, 300
_ORACLE_LAMS = (0.40, 0.28, 0.20, 0.141, 0.099, 0.070, 0.049, 0.035)


def _oracle_problems(b: int = 16):
    """b seeded small-sample problems (EBIC picks interior lams there):
    (corr stack, true adjacency stack)."""
    Ss, adjs = [], []
    for i in range(b):
        rng = np.random.default_rng(100 + i)
        theta = glasso.random_sparse_precision(
            _ORACLE_D, density=0.2, rng=rng)
        x = np.asarray(sampler.sample_ggm(
            jax.random.key(100 + i), _ORACLE_N, np.linalg.inv(theta)))
        Ss.append(np.corrcoef(x, rowvar=False).astype(np.float32))
        adj = np.abs(theta) > 1e-8
        np.fill_diagonal(adj, False)
        adjs.append(adj)
    return jnp.asarray(np.stack(Ss)), np.stack(adjs)


def _fused_select(conv_tol: float):
    """One jitted launch: warm-started grid scan + on-device EBIC pick."""
    @jax.jit
    def run(S):
        solve = glasso_path_batch(
            S, jnp.asarray(_ORACLE_LAMS, jnp.float32),
            n_steps=_ORACLE_STEPS, conv_tol=conv_tol,
            support_tol=glasso.SUPPORT_TOL)
        idx = select_ebic(ebic_scores(
            solve.logdet, solve.tr_s_theta, solve.edges, _ORACLE_N,
            _ORACLE_D, PathPlan().ebic_gamma))
        sel = jnp.take_along_axis(
            solve.support, idx[None, :, None, None], axis=0)[0]
        return sel, idx, solve.iters

    return run


def _host_ebic_select(S):
    """One cold full-budget glasso launch per lam, EBIC picked on the host
    in float64."""
    Sh = np.asarray(S, np.float64)
    sups, scores = [], []
    for lam in _ORACLE_LAMS:
        th = np.asarray(glasso.glasso_batch(S, lam, n_steps=_ORACLE_STEPS),
                        np.float64)
        sup = np.asarray(glasso.support_from_theta(
            jnp.asarray(th), glasso.SUPPORT_TOL))
        e = sup.sum(axis=(-2, -1)) // 2
        _, logdet = np.linalg.slogdet(th)
        tr = (Sh * th).sum(axis=(-2, -1))
        scores.append(-_ORACLE_N * (logdet - tr) + e * (
            np.log(_ORACLE_N) + 2.0 * np.log(_ORACLE_D)))
        sups.append(sup)
    idx = np.argmin(np.stack(scores), axis=0)
    return np.stack(sups)[idx, np.arange(S.shape[0])]


def _mean_f1(est, true) -> float:
    tp = (est & true).sum(axis=(-2, -1))
    denom = est.sum(axis=(-2, -1)) + true.sum(axis=(-2, -1))
    return float(np.mean(2.0 * tp / np.maximum(denom, 1)))


@pytest.fixture(scope="module")
def oracle_sweeps():
    S, true_adj = _oracle_problems()
    fused = _fused_select(PathPlan().conv_tol)
    jax.block_until_ready(fused(S))  # compile outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        out = jax.block_until_ready(fused(S))
    return (S, true_adj, jax.device_get(out),
            jax.device_get(_fused_select(0.0)(S)))


@pytest.mark.parametrize("claim", ["selection_matches_oracle_support",
                                   "f1_not_worse_than_host_ebic",
                                   "early_exit_saves_iterations"])
def test_path_early_exit_against_oracle(oracle_sweeps, claim):
    """At the default conv_tol the fused sweep (run once under the d2h
    guard) selects exactly the full-budget oracle's supports on the
    seeded batch, scores no worse than per-lam cold solves with a host
    EBIC pick, and spends under half the cold iteration budget."""
    S, true_adj, (sel, idx, iters), (o_sel, o_idx, _) = oracle_sweeps
    if claim == "selection_matches_oracle_support":
        assert (sel == o_sel).all() and (idx == o_idx).all()
    elif claim == "f1_not_worse_than_host_ebic":
        host = _host_ebic_select(S)
        assert (_mean_f1(sel.astype(bool), true_adj)
                >= _mean_f1(host.astype(bool), true_adj) - 1e-6)
    else:
        per_lam = iters.astype(np.float64).mean(axis=1)
        assert per_lam.sum() < 0.5 * len(_ORACLE_LAMS) * _ORACLE_STEPS
