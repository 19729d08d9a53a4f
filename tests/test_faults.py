"""Fault-tolerant wire plane: deterministic fault injection, masked-Gram
graceful degradation, and retry accounting.

Covers the FaultPlan draw layer (``core.faults``), the masked estimator
chain (``core.estimators`` effective counts / safe denominators), the
voided-edge Kruskal (``core.chow_liu``), the streaming per-machine
truncation (``core.streaming``), and the sweep engine integration
(``core.experiments``: zero-fault bit-identity, telemetry on the single
host sync, measured retry bits). The multi-device parity gate lives in
``test_distributed.py::test_fault_wire_trial_plane_parity``.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import estimators, quantizers
from repro.core.chow_liu import kruskal_forest, kruskal_mst
from repro.core.experiments import TrialPlan, run_trials
from repro.core.faults import FaultPlan, fault_trial_keys
from repro.core.strategy import FIG3_STRATEGIES, Strategy
from repro.core.streaming import StreamingGram


# --------------------------------------------------------------------------
# FaultPlan: validation, hashability, deterministic draws
# --------------------------------------------------------------------------

def test_fault_plan_validation_and_hashability():
    fp = FaultPlan(dropout=0.1, straggle=0.2, bitflip=0.01, retries=2,
                   machines=4, seed=3)
    assert hash(fp) == hash(FaultPlan(dropout=0.1, straggle=0.2,
                                      bitflip=0.01, retries=2, machines=4,
                                      seed=3))
    assert fp.channels == 6  # 2 + 2 * retries
    assert not fp.is_null and FaultPlan().is_null
    assert fp.n_machines(8) == 4
    assert list(np.asarray(fp.feature_machines(8))) == [0, 0, 1, 1, 2, 2,
                                                        3, 3]
    with pytest.raises(ValueError):
        fp.n_machines(6)  # 4 does not divide 6
    with pytest.raises(ValueError):
        FaultPlan(dropout=1.5)
    with pytest.raises(ValueError):
        FaultPlan(straggle_frac=0.0)
    with pytest.raises(ValueError):
        FaultPlan(retries=-1)
    with pytest.raises(ValueError):
        FaultPlan(machines=0)
    # TrialPlan validates machine divisibility at construction
    with pytest.raises(ValueError):
        TrialPlan(d=10, ns=(32,), strategies=FIG3_STRATEGIES[:1],
                  faults=FaultPlan(machines=4))
    with pytest.raises(TypeError):
        TrialPlan(d=8, ns=(32,), strategies=FIG3_STRATEGIES[:1],
                  faults="dropout")


def test_fault_draws_deterministic_and_bucket_stable():
    fp = FaultPlan(dropout=0.3, straggle=0.4, bitflip=0.05, machines=4,
                   seed=9)
    keys = fault_trial_keys(fp, 6)
    d = 8
    n_rows_a, flip_a, tele_a = fp.draw_batch(keys, 64, 50, d)
    n_rows_b, flip_b, tele_b = fp.draw_batch(keys, 64, 50, d)
    np.testing.assert_array_equal(np.asarray(n_rows_a), np.asarray(n_rows_b))
    np.testing.assert_array_equal(np.asarray(tele_a), np.asarray(tele_b))
    np.testing.assert_array_equal(np.asarray(flip_a), np.asarray(flip_b))
    # bit-flip mask is ROW-keyed: the padded draw agrees with the smaller
    # bucket on the shared prefix (the sampler's bucket-stability contract)
    _, flip_small, _ = fp.draw_batch(keys, 32, 30, d)
    np.testing.assert_array_equal(np.asarray(flip_a)[:, :32],
                                  np.asarray(flip_small))
    # n_rows is machine-blocked: features of one machine share one count
    nr = np.asarray(n_rows_a)
    for m in range(4):
        blk = nr[:, 2 * m:2 * m + 2]
        assert (blk[:, 0] == blk[:, 1]).all()
    # telemetry is integer-valued
    assert (np.asarray(tele_a) == np.round(np.asarray(tele_a))).all()
    # a zero-fault plan draws full-delivery masks and no flips
    nz, fz, tz = FaultPlan(machines=4).draw_batch(
        fault_trial_keys(FaultPlan(machines=4), 6), 64, 50, d)
    assert fz is None
    assert (np.asarray(nz) == 50).all()
    assert (np.asarray(tz) == 0.0).all()


def test_fault_keys_independent_of_sampler_seed():
    """The fault root folds _FAULT_ROOT, so equal seeds do not collide
    with the sampler's per-trial streams."""
    from repro.core.faults import _FAULT_ROOT
    fkeys = fault_trial_keys(FaultPlan(seed=5), 4)
    skeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.key(5), jnp.arange(4, dtype=jnp.uint32))
    assert not np.array_equal(jax.random.key_data(fkeys),
                              jax.random.key_data(skeys))
    assert _FAULT_ROOT == 0x6661756C


# --------------------------------------------------------------------------
# Masked estimator chain (tentpole center math + satellite 1)
# --------------------------------------------------------------------------

def _host_masked_reference(x, n_rows, method, rate=4):
    """Per-pair prefix-intersection reference: entry (j, k) uses exactly
    the first min(n_rows[j], n_rows[k]) samples."""
    n, d = x.shape
    gram = np.zeros((d, d), np.float64)
    if method == "sign":
        u = np.where(x >= 0, 1.0, -1.0)
    elif method == "persymbol":
        q = quantizers.PerSymbolQuantizer(rate)
        u = np.asarray(q.quantize(jnp.asarray(x)), np.float64)
    else:
        u = np.asarray(x, np.float64)
    for j in range(d):
        for k in range(d):
            m = min(int(n_rows[j]), int(n_rows[k]))
            gram[j, k] = np.dot(u[:m, j], u[:m, k])
    return gram


@pytest.mark.parametrize("strategy", [
    Strategy("sign", wire="int8"),
    Strategy("sign", wire="packed"),
    Strategy("persymbol", rate=4),
    Strategy("original"),
])
def test_masked_payload_gram_matches_prefix_reference(strategy):
    rng = np.random.default_rng(0)
    n, d = 64, 6
    x = rng.standard_normal((n, d)).astype(np.float32)
    n_rows = np.array([64, 64, 32, 32, 0, 0], np.int32)  # one dropped pair
    payload = estimators.strategy_payload(
        jnp.asarray(x), strategy, n_rows=jnp.asarray(n_rows))
    gram = estimators.payload_gram(
        payload, strategy, n_rows=jnp.asarray(n_rows))
    ref = _host_masked_reference(x, n_rows, strategy.method,
                                 rate=strategy.rate)
    np.testing.assert_allclose(np.asarray(gram), ref, atol=2e-3)
    # effective counts are the pairwise prefix intersections
    n_eff = np.asarray(estimators.effective_counts(jnp.asarray(n_rows)))
    assert n_eff[0, 0] == 64 and n_eff[0, 2] == 32 and n_eff[0, 4] == 0


def test_effective_counts_batched():
    n_rows = jnp.asarray([[4, 2, 0], [8, 8, 8]], jnp.int32)
    n_eff = np.asarray(estimators.effective_counts(n_rows))
    assert n_eff.shape == (2, 3, 3)
    assert n_eff[0, 0, 1] == 2 and n_eff[0, 1, 2] == 0 and n_eff[0, 0, 0] == 4
    assert (n_eff[1] == 8).all()


@pytest.mark.parametrize("method", ["sign", "persymbol", "original"])
def test_corr_from_gram_neutral_when_starved(method):
    """Satellite 1 regression: n_eff of 0 or 1 (an all-dropped machine)
    must produce the NEUTRAL correlation (identity entries), never NaN."""
    d = 4
    # machine owning features 2,3 fully dropped; feature 1 has ONE sample.
    # A realized masked Gram has diag == n_rows (unit-variance codes) and
    # zero in every voided entry.
    n_rows = jnp.asarray([8, 1, 0, 0], jnp.int32)
    gram = jnp.diag(n_rows.astype(jnp.float32))
    n_eff = estimators.effective_counts(n_rows)
    rho = np.asarray(estimators.corr_from_gram(gram, n_eff, method))
    assert np.isfinite(rho).all(), rho
    # voided off-diagonals are exactly 0, diagonal exactly 1
    assert rho[0, 2] == 0.0 and rho[2, 3] == 0.0 and rho[0, 1] == 0.0
    np.testing.assert_array_equal(np.diag(rho), np.ones(d, np.float32))


@pytest.mark.parametrize("method", ["sign", "persymbol", "original"])
def test_weights_from_gram_neutral_when_starved(method):
    """Voided pairs get weight exactly 0 (MI >= 0, so a voided edge can
    never win the MWST over any surviving edge)."""
    d = 4
    gram = jnp.zeros((d, d), jnp.float32)
    n_rows = jnp.asarray([8, 8, 0, 1], jnp.int32)
    w = np.asarray(estimators.weights_from_gram(
        gram, estimators.effective_counts(n_rows), method))
    assert np.isfinite(w).all(), w
    assert w[0, 2] == 0.0 and w[2, 3] == 0.0 and w[0, 3] == 0.0


@pytest.mark.parametrize("method", ["sign", "persymbol", "original"])
def test_weights_from_gram_normalized_matches_raw(method):
    """normalized=True ingests the pre-divided statistic (the serving
    plane's host float64 normalization); at a pow2 count the division is
    exact, so the two forms must agree bit for bit — including the
    n_eff < 2 neutralization."""
    rng = np.random.default_rng(0)
    d, n = 5, 64.0
    x = rng.standard_normal((int(n), d)).astype(np.float32)
    base = np.where(x >= 0, 1, -1).astype(np.float32) \
        if method == "sign" else x
    gram = jnp.asarray(base.T @ base)
    n_op = jnp.full((1, 1), n, jnp.float32)      # ndim >= 2: n_eff branch
    a = estimators.weights_from_gram(gram, n_op, method)
    b = estimators.weights_from_gram(gram / n, n_op, method,
                                     normalized=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    starved = jnp.full((1, 1), 1.0, jnp.float32)
    w = np.asarray(estimators.weights_from_gram(
        gram / n, starved, method, normalized=True))
    assert (w == 0.0).all()


def test_all_dropped_sweep_degrades_gracefully():
    """Satellite 1 end-to-end: dropout=1.0 voids every machine; the sweep
    still completes with finite metrics and error rate exactly 1."""
    plan = TrialPlan(d=8, ns=(32,), strategies=FIG3_STRATEGIES[:2], reps=4,
                     faults=FaultPlan(dropout=1.0, machines=4))
    r = run_trials(plan)
    for lab in r.error_rate:
        assert r.error_rate[lab] == [1.0]
        assert all(np.isfinite(v) for v in r.edit_distance[lab])
    assert r.faults[0]["dropped_machines"] == 4.0


# --------------------------------------------------------------------------
# Satellite 2: host Kruskal under masked / non-finite weights
# --------------------------------------------------------------------------

def test_kruskal_forest_skips_non_finite_edges():
    w = np.array([
        [0.0, 3.0, np.nan, 1.0],
        [3.0, 0.0, 2.0, np.inf],
        [np.nan, 2.0, 0.0, 0.5],
        [1.0, np.inf, 0.5, 0.0],
    ])
    edges = kruskal_mst(w)
    # voided edges (0,2) and (1,3) never enter; the rest span
    assert (0, 2) not in edges and (1, 3) not in edges
    assert len(edges) == 3
    assert set(edges) == {(0, 1), (1, 2), (0, 3)}
    # all-voided input yields the empty forest, not a NaN-ordered tree
    assert kruskal_mst(np.full((3, 3), np.nan)) == []
    # threshold still applies among the finite edges
    assert kruskal_forest(w, min_weight=1.5) == [(0, 1), (1, 2)]


def test_host_kruskal_matches_device_under_dropout():
    """Satellite 2 pin: mst='host_kruskal' is metric-identical to the
    device Boruvka path on fault-masked weight matrices."""
    plan = TrialPlan(
        d=8, ns=(32, 128), strategies=FIG3_STRATEGIES, reps=8, seed0=11,
        faults=FaultPlan(dropout=0.3, straggle=0.3, machines=4, seed=2))
    rd = run_trials(plan)
    rk = run_trials(plan, mst="host_kruskal")
    assert rk.host_syncs == 1
    for lab in rd.error_rate:
        assert rd.error_rate[lab] == rk.error_rate[lab], lab
        assert rd.edit_distance[lab] == rk.edit_distance[lab], lab
        assert rd.edge_f1[lab] == rk.edge_f1[lab], lab
    assert rd.faults == rk.faults


# --------------------------------------------------------------------------
# Satellite 3: streaming batch updates with empty / truncated machines
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["sign", "persymbol"])
def test_update_codes_batch_truncated_equals_sequential(method):
    rng = np.random.default_rng(1)
    m, n_b, d = 4, 24, 5
    x = rng.standard_normal((m, n_b, d)).astype(np.float32)
    if method == "sign":
        codes = np.asarray(quantizers.sign_codes(jnp.asarray(x)))
    else:
        q = quantizers.PerSymbolQuantizer(3)
        codes = np.asarray(q.encode(jnp.asarray(x)).astype(jnp.int8))
    n_valid = np.array([24, 0, 7, 16], np.int32)  # full / EMPTY / prefixes
    acc = StreamingGram(d=d, method=method, rate=3)
    acc.update_codes_batch(jnp.asarray(codes), n_valid=n_valid)
    ref = StreamingGram(d=d, method=method, rate=3)
    for i in range(m):
        if n_valid[i]:
            ref.update_codes(jnp.asarray(codes[i, :n_valid[i]]))
    assert acc.n == ref.n == int(n_valid.sum())
    np.testing.assert_allclose(np.asarray(acc.gram), np.asarray(ref.gram),
                               atol=1e-5)


def test_update_packed_batch_truncated_equals_sequential():
    rng = np.random.default_rng(2)
    m, n_b, d = 3, 32, 6
    x = rng.standard_normal((m, n_b, d)).astype(np.float32)
    strat = Strategy("sign", wire="packed")
    payloads = jnp.stack([
        estimators.strategy_payload(jnp.asarray(x[i]), strat)
        for i in range(m)])  # (m, d, n_b // 8) uint8
    n_valid = np.array([32, 0, 13], np.int32)  # full / empty / odd prefix
    acc = StreamingGram(d=d, method="sign")
    acc.update_packed_batch(payloads, n_b, n_valid=n_valid)
    ref = StreamingGram(d=d, method="sign")
    for i in range(m):
        if n_valid[i]:
            ref.update_codes(
                quantizers.sign_codes(jnp.asarray(x[i, :n_valid[i]])))
    assert acc.n == ref.n == int(n_valid.sum())
    np.testing.assert_allclose(np.asarray(acc.gram), np.asarray(ref.gram),
                               atol=1e-5)
    # and the no-fault call is unchanged by the new kwarg
    a = StreamingGram(d=d, method="sign").update_packed_batch(payloads, n_b)
    b = StreamingGram(d=d, method="sign")
    for i in range(m):
        b.update_packed(payloads[i], n_b)
    np.testing.assert_array_equal(np.asarray(a.gram), np.asarray(b.gram))


# --------------------------------------------------------------------------
# Sweep engine integration (tentpole acceptance on one device)
# --------------------------------------------------------------------------

def test_zero_fault_plan_bit_identical_to_no_plan():
    """A FaultPlan with all probabilities zero runs the fault path yet
    reproduces the faultless sweep bit for bit (all-true masks are the
    identity through every where/mask op)."""
    strats = FIG3_STRATEGIES
    base = TrialPlan(d=8, ns=(32, 100), strategies=strats, reps=6, seed0=3)
    fault = TrialPlan(d=8, ns=(32, 100), strategies=strats, reps=6,
                      seed0=3, faults=FaultPlan(machines=4, retries=1))
    with jax.transfer_guard_device_to_host("disallow"):
        r0 = run_trials(base)
        rz = run_trials(fault)
    assert r0.host_syncs == rz.host_syncs == 1
    for lab in r0.error_rate:
        assert r0.error_rate[lab] == rz.error_rate[lab], lab
        assert r0.edit_distance[lab] == rz.edit_distance[lab], lab
        assert r0.edge_f1[lab] == rz.edge_f1[lab], lab
    # the telemetry rode the same sync and reports zero faults, and the
    # retry accounting measured zero retransmissions
    assert rz.faults is not None and r0.faults is None
    for st in rz.faults:
        assert st["dropped_machines"] == 0.0
        assert st["retransmissions"] == [0.0]
    for lab, reports in rz.comm.items():
        assert all(c.retry_bytes == 0.0 for c in reports)


def test_bitflip_changes_sign_payloads_only():
    """bitflip corrupts the 1-bit wire (both int8 and packed layouts see
    the SAME flips) but leaves per-symbol/original strategies untouched."""
    fp = FaultPlan(bitflip=0.2, machines=4, seed=1)
    strats = (Strategy("sign", wire="packed"), Strategy("persymbol", rate=4),
              Strategy("original"))
    base = TrialPlan(d=8, ns=(64,), strategies=strats, reps=8, seed0=3)
    flip = TrialPlan(d=8, ns=(64,), strategies=strats, reps=8, seed0=3,
                     faults=fp)
    r0, rf = run_trials(base), run_trials(flip)
    # heavy flips must hurt the sign wire at n=64 (same draws otherwise)
    assert rf.edit_distance["sign"][0] > r0.edit_distance["sign"][0]
    # flips never touch the R-bit or float wires
    for lab in ("R4", "original"):
        assert rf.error_rate[lab] == r0.error_rate[lab], lab
        assert rf.edit_distance[lab] == r0.edit_distance[lab], lab
    # the int8 sign layout sees the SAME row-keyed flip mask: a separate
    # plan (same seeds, shared data convention) degrades identically
    rf_i8 = run_trials(TrialPlan(
        d=8, ns=(64,), strategies=(Strategy("sign", wire="int8"),),
        reps=8, seed0=3, faults=fp))
    assert rf_i8.error_rate["sign"] == rf.error_rate["sign"]
    assert rf_i8.edit_distance["sign"] == rf.edit_distance["sign"]


def test_retry_accounting_measured_not_estimated():
    """Retry bits come from the REALIZED retransmission counts: retries
    reduce the realized drop rate, every retry byte is accounted, and the
    counts match the telemetry exactly."""
    strats = FIG3_STRATEGIES[:2]
    mk = lambda r, seed=4: TrialPlan(
        d=8, ns=(64,), strategies=strats, reps=16, seed0=3,
        faults=FaultPlan(dropout=0.4, machines=4, retries=r, seed=seed))
    r0, r2 = run_trials(mk(0)), run_trials(mk(2))
    # retries re-deliver payloads: strictly fewer machines end up dropped
    assert r2.faults[0]["dropped_machines"] < r0.faults[0]["dropped_machines"]
    # no-retry plans carry no retry accounting
    for c in r0.comm["sign"]:
        assert c.retry_bytes == 0.0 and c.retry_rounds == 0
    # retry bytes == mean retransmitted machines x per-machine bytes
    stats = r2.faults[0]
    mean_retrans = sum(stats["retransmissions"])
    for lab, reports in r2.comm.items():
        c = reports[0]
        assert c.retry_rounds == 2
        np.testing.assert_allclose(
            c.retry_bytes, mean_retrans * c.wire_bytes / 4, rtol=1e-6)
        assert c.retry_collectives == pytest.approx(
            sum(stats["retry_rounds_used"]), rel=1e-6)
        assert c.retry_bits == 8.0 * c.retry_bytes
    # overhead (wire vs logical) excludes retry bits — they are a separate
    # honest column
    assert r2.comm["sign"][0].overhead == r0.comm["sign"][0].overhead


def test_fault_sweep_shares_draws_across_strategies():
    """All strategies degrade on the SAME fault realization (the fault
    twin of the shared-data convention): with full dropout of one machine
    set, every strategy reports identical telemetry."""
    plan = TrialPlan(
        d=8, ns=(32, 64), strategies=FIG3_STRATEGIES, reps=6, seed0=3,
        faults=FaultPlan(dropout=0.3, straggle=0.5, machines=4, seed=8))
    r = run_trials(plan)
    assert len(r.faults) == 2
    # telemetry is per-n (fault draws are round/machine keyed, not
    # n-keyed, so equal across ns here — the point: it's one realization)
    assert r.faults[0]["dropped_machines"] == r.faults[1]["dropped_machines"]
    # sparse plans ride the same fault plane
    sp = (Strategy("sign", structure="sparse", lam=0.1),)
    plan_sp = TrialPlan(d=8, ns=(64,), strategies=sp, reps=6, seed0=3,
                        tree="sparse",
                        faults=FaultPlan(dropout=0.3, machines=4, seed=8))
    rs = run_trials(plan_sp)
    assert rs.faults is not None and rs.host_syncs == 1
    for lab in rs.error_rate:
        assert all(np.isfinite(v) for v in rs.error_rate[lab])


# --------------------------------------------------------------------------
# Degradation gate: heavy dropout, with and without bounded retry
# --------------------------------------------------------------------------

_DEGRADE_STRATS = (Strategy("sign", wire="packed"),
                   Strategy("persymbol", rate=4), Strategy("original"))


@pytest.fixture(scope="module")
def dropout_ladder():
    """The same d=16, 4-machine sweep lossless, at 25% dropout, and at 25%
    dropout healed by 2 retries, each under the d2h transfer guard."""
    def sweep(faults):
        plan = TrialPlan(d=16, ns=(128, 512), strategies=_DEGRADE_STRATS,
                         reps=32, seed0=7, faults=faults)
        with jax.transfer_guard_device_to_host("disallow"):
            return run_trials(plan)

    return (sweep(None),
            sweep(FaultPlan(dropout=0.25, machines=4, seed=1)),
            sweep(FaultPlan(dropout=0.25, retries=2, machines=4, seed=1)))


@pytest.mark.parametrize("claim", ["degradation_bounded",
                                   "no_collapse_without_retry",
                                   "one_sync_per_sweep"])
def test_dropout_degrades_gracefully(dropout_ladder, claim):
    """25% dropout healed by 2 retries stays within 0.15 of the lossless
    error at the largest n; without retry the error stays off the
    ceiling; the fault path keeps one host sync per sweep."""
    lossless, dropped, retried = dropout_ladder
    labels = [s.label for s in _DEGRADE_STRATS]
    if claim == "degradation_bounded":
        for lab in labels:
            assert (retried.error_rate[lab][-1]
                    <= lossless.error_rate[lab][-1] + 0.15), lab
    elif claim == "no_collapse_without_retry":
        assert all(dropped.error_rate[lab][-1] < 1.0 for lab in labels)
    else:
        assert [r.host_syncs for r in dropout_ladder] == [1, 1, 1]
