"""Statistic estimators used by the central machine (paper §4.2, §5).

All estimators take the full received code matrix U of shape (n, d) and
produce pairwise (d, d) statistic matrices; they are pure and jit-able.
The pairwise contraction U^T U is the compute hot spot: every estimator
routes it through :class:`repro.core.gram.GramEngine` (Pallas kernels on
TPU, plain XLA matmuls on CPU, numpy host reference), so the same code
serves as both the production path and the kernels' reference semantics.
Pass ``engine=`` to pin a backend; ``None`` uses the process default.

The declarative entry points decompose into the three stages every
pipeline in the repo shares (the same decomposition
``core.distributed.WirePlan`` runs over real collectives):

* :func:`strategy_payload` — **encode**: raw samples -> the strategy's
  wire payload (±1 int8 signs, int8 bin codes, dense packed bits, or raw
  f32 for the unquantized baseline), valid-length masked;
* :func:`payload_gram`    — **central contraction**: payload -> (d, d)
  Gram through the engine's (batched) kernels, straight off the wire
  bytes where the format allows it;
* :func:`weights_from_gram` — **central estimate**: Gram + sample count
  -> Chow-Liu weights (eqs. 1/4/30), shared verbatim by the batch,
  streaming, distributed and trial-plane paths.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.spans import span
from .gram import GramEngine, resolve_engine
from .strategy import Strategy


def theta_hat(u: jax.Array, *, engine: GramEngine | None = None) -> jax.Array:
    """UMVE of theta_jk = Pr(u_j u_k = 1) from sign data (eq. 8).

    With u in {-1,+1}: I(u_j u_k = 1) = (1 + u_j u_k)/2, so
    theta_hat = 1/2 + (U^T U) / (2n).
    """
    n = u.shape[0]
    gram = resolve_engine(engine).gram(u)
    return 0.5 + gram / (2.0 * n)


def theta_hat_packed(
    packed: jax.Array, n: int, *, engine: GramEngine | None = None
) -> jax.Array:
    """theta_hat (eq. 8) straight from the 1-bit packed wire payload —
    (d, ceil(n/8)) uint8, ``quantizers.pack_codes`` layout — via the
    packed sign Gram. Exact: equals :func:`theta_hat` on the unpacked u."""
    gram = resolve_engine(engine).packed_sign_gram(packed, n)
    return 0.5 + gram / (2.0 * n)


def theta_from_rho(rho: jax.Array) -> jax.Array:
    """theta = 1/2 + arcsin(rho)/pi (eq. 3)."""
    return 0.5 + jnp.arcsin(jnp.clip(rho, -1.0, 1.0)) / jnp.pi


def rho_from_theta(theta: jax.Array) -> jax.Array:
    """Inverse of eq. (3): rho = sin(pi (theta - 1/2))."""
    return jnp.sin(jnp.pi * (theta - 0.5))


def binary_entropy(p: jax.Array) -> jax.Array:
    """h(p) in bits (eq. 5), safe at {0, 1}."""
    # epsilon must be representable in f32: 1 - 1e-12 rounds to 1.0 in f32
    # and would give 0 * log(0) = NaN on the (irrelevant) diagonal.
    p = jnp.clip(p, 1e-7, 1.0 - 1e-7)
    return -(p * jnp.log2(p) + (1.0 - p) * jnp.log2(1.0 - p))


def mi_sign(theta: jax.Array) -> jax.Array:
    """I(u_j; u_k) = 1 - h(theta) in bits (eq. 4)."""
    return 1.0 - binary_entropy(theta)


def mi_gaussian(rho: jax.Array) -> jax.Array:
    """I(x_j; x_k) = -1/2 ln(1 - rho^2) (eq. 1).

    The clip must be representable in f32: 1 - 1e-12 rounds to 1.0 and the
    (MWST-irrelevant) diagonal would become inf."""
    r2 = jnp.clip(jnp.square(rho), 0.0, 1.0 - 1e-7)
    return -0.5 * jnp.log1p(-r2)


def sample_correlation(
    u: jax.Array, *, engine: GramEngine | None = None
) -> jax.Array:
    """rho_bar_q = (1/n) sum_i u_j^(i) u_k^(i) (eqs. 31/32).

    Note the paper's estimator deliberately does NOT renormalize by sample
    variances — variables are assumed standardized (Q_jj = 1) and the central
    machine treats quantized codes as if Gaussian.
    """
    n = u.shape[0]
    return resolve_engine(engine).gram(u) / n


def rho_squared_unbiased(rho_bar: jax.Array, n: int) -> jax.Array:
    """Unbiased estimator of rho^2 (eq. 30): n/(n+1) (rho_bar^2 - 1/n)."""
    return (n / (n + 1.0)) * (jnp.square(rho_bar) - 1.0 / n)


def sign_method_weights(
    u_signs: jax.Array, *, engine: GramEngine | None = None
) -> jax.Array:
    """Edge-weight matrix for Chow-Liu under the sign method: hat I(u_j; u_k).

    Any strictly increasing transform of |theta - 1/2| yields the same MWST
    (Kruskal depends only on the order); we return the MI itself for
    interpretability and parity with the paper.
    """
    return mi_sign(theta_hat(u_signs, engine=engine))


def sign_method_weights_packed(
    packed: jax.Array, n: int, *, engine: GramEngine | None = None
) -> jax.Array:
    """Sign-method Chow-Liu weights computed directly on the 1-bit packed
    payload (no unpack): mi_sign(theta_hat_packed(...))."""
    return mi_sign(theta_hat_packed(packed, n, engine=engine))


def persymbol_method_weights(
    u_centroids: jax.Array, *, engine: GramEngine | None = None
) -> jax.Array:
    """Edge weights for Chow-Liu under per-symbol quantization (§5).

    Estimates rho^2 via eq. (30) applied to the quantized sample correlation
    (eq. 32) and maps through the Gaussian MI (eq. 1). MI is monotone in
    rho^2, so using rho^2_hat directly is order-equivalent; we report MI.
    """
    n = u_centroids.shape[0]
    return weights_from_gram(
        resolve_engine(engine).gram(u_centroids), n, "persymbol")


def persymbol_code_weights(
    codes: jax.Array,
    centroids: jax.Array,
    *,
    engine: GramEngine | None = None,
) -> jax.Array:
    """Per-symbol weights straight from int8 bin codes + codebook: the
    centroid decode happens inside the Gram backend (in-kernel on pallas),
    so no decoded copy of U is materialized."""
    n = codes.shape[0]
    return weights_from_gram(
        resolve_engine(engine).code_gram(codes, centroids), n, "persymbol")


def gaussian_weights(
    x: jax.Array, *, engine: GramEngine | None = None
) -> jax.Array:
    """Centralized (unquantized) baseline: MI from the sample correlation."""
    return weights_from_gram(
        resolve_engine(engine).gram(x), x.shape[0], "original")


def effective_counts(n_rows) -> jax.Array:
    """(..., d) per-feature delivered-row counts -> (..., d, d) effective
    PAIRWISE sample counts: n_eff[j, k] = min(n_rows[j], n_rows[k]).

    Exact (not a bound) because every fault mask is a PREFIX mask per
    feature column — dropout voids a whole column, straggling truncates it
    to its first rows — so the row set contributing to Gram entry (j, k)
    is exactly the first min(n_rows[j], n_rows[k]) rows. This is the ``n``
    operand :func:`weights_from_gram` / :func:`corr_from_gram` normalize
    by under a :class:`~repro.core.faults.FaultPlan` (under rowblock
    placement different machines' dropouts void different Gram blocks, and
    this matrix is what keeps each surviving block honestly normalized).
    """
    counts = jnp.asarray(n_rows, jnp.float32)
    return jnp.minimum(counts[..., :, None], counts[..., None, :])


def weights_from_gram(gram: jax.Array, n, method, *,
                      normalized: bool = False) -> jax.Array:
    """Central-machine estimate: raw Gram + sample count -> Chow-Liu weights.

    THE shared tail of every pipeline (batch estimators, streaming
    accumulator, distributed wire runtime, trial plane): ``gram`` is the
    ((..., d, d)) contraction of whatever the wire delivered, ``n`` the
    sample count it sums over (a python int, or a traced f32 scalar under
    the trial plane's valid-length masking, or the (..., d, d) per-entry
    effective-count matrix of :func:`effective_counts` under a fault
    plan), ``method`` a method string or a
    :class:`~repro.core.strategy.Strategy`.

    * ``'sign'``      — eq. 8 UMVE theta_hat -> MI of signs (eq. 4);
    * ``'persymbol'`` — eq. 32 quantized correlation -> unbiased rho^2
      (eq. 30) -> Gaussian MI (eq. 1);
    * ``'original'``  — sample correlation -> Gaussian MI (eq. 1).

    With a per-entry ``n`` the division uses a safe denominator
    (max(n_eff, 1)) and entries whose effective count is < 2 — a dropped
    machine's whole row/column block — are neutralized to weight 0: MI
    weights are >= 0, so a voided edge can never win the MWST, and the
    solve stays finite however many machines were lost.

    ``normalized=True`` declares that ``gram`` is ALREADY the
    per-sample statistic gram / max(n, 1) — the caller divided on the
    host (e.g. the serving plane's int64 counts normalized in float64,
    which f32 arithmetic would round past 2^24 samples). ``n`` is then
    used only for the persymbol bias correction and the n_eff < 2
    neutralization, both insensitive to f32 rounding of huge counts.
    """
    method = getattr(method, "method", method)
    n_eff = None
    if jnp.ndim(n) >= 2:
        n_eff = jnp.asarray(n, jnp.float32)
        n = jnp.maximum(n_eff, 1.0)
    if method == "original":
        w = mi_gaussian(gram if normalized else gram / n)
    elif method == "sign":
        w = mi_sign((0.5 + gram / 2.0) if normalized
                    else (0.5 + gram / (2.0 * n)))
    elif method == "persymbol":
        rho_bar = gram if normalized else gram / n
        # the clip bound must be representable in f32 (1 - 1e-9 rounds to
        # 1.0 and the MWST-irrelevant diagonal would become inf) — same
        # guard as mi_gaussian
        r2 = jnp.clip(rho_squared_unbiased(rho_bar, n), 0.0, 1.0 - 1e-7)
        w = -0.5 * jnp.log1p(-r2)
    else:
        raise ValueError(f"unknown method {method!r}")
    if n_eff is not None:
        w = jnp.where(n_eff >= 2.0, w, 0.0)
    return w


def corr_from_gram(gram: jax.Array, n, method) -> jax.Array:
    """Central-machine estimate for SPARSE structures: raw Gram + sample
    count -> the correlation statistic the glasso solve ingests.

    The sparse twin of :func:`weights_from_gram` (same operands, same
    batched shapes, same method dispatch — ``method`` a method string or a
    :class:`~repro.core.strategy.Strategy`):

    * ``'original'`` / ``'persymbol'`` — the sample correlation gram / n
      (eqs. 31/32; PSD by construction, no repair needed);
    * ``'sign'`` — the arcsine law inverted on the eq.-8 statistic:
      rho = sin(pi * gram / (2n)). The elementwise `sin` transform of a
      sample sign-Gram is NOT guaranteed PSD at small n, so the result is
      eigen-clipped back to a valid correlation matrix
      (``glasso.nearest_correlation``) before it reaches the `-logdet`
      objective.

    ``n`` may also be the (..., d, d) per-entry effective-count matrix of
    :func:`effective_counts` (the fault plane's masked Gram): the division
    then uses a safe denominator (max(n_eff, 1)) and DEGENERATE entries —
    effective count 0 or 1, e.g. an all-dropped machine's whole block —
    are neutralized to the identity's entries (0 off-diagonal, 1 on it)
    instead of propagating 0/0 NaNs: a fully-lost feature enters the
    solve as an isolated unit-variance variable and the glasso stays
    finite.
    """
    from .glasso import nearest_correlation

    method = getattr(method, "method", method)
    n_eff = None
    if jnp.ndim(n) >= 2:
        n_eff = jnp.asarray(n, jnp.float32)
        n = jnp.maximum(n_eff, 1.0)
    if method in ("original", "persymbol"):
        rho = gram / n
    elif method == "sign":
        rho = jnp.sin(jnp.pi * gram / (2.0 * n))
    else:
        raise ValueError(f"unknown method {method!r}")
    if n_eff is not None:
        rho = jnp.where(n_eff >= 2.0, rho,
                        jnp.eye(gram.shape[-1], dtype=rho.dtype))
    if method == "sign":
        return nearest_correlation(rho)
    return rho


# ---------------------------------------------------------------------------
# Channel plane (repro.comm.channel): MAC superposition + budgeted rates
# ---------------------------------------------------------------------------


def mac_delivered_rows(channel, n_pad: int, n_valid=None) -> jax.Array:
    """Lossless per-machine delivered-row counts under the MAC row-block
    partition: machine m owns the contiguous padded rows
    ``[m*b, (m+1)*b)`` (``b = n_pad / machines``), so with ``n_valid``
    real samples it delivers ``clip(n_valid - m*b, 0, b)`` of them.
    (machines,) int32; they sum to exactly ``n_valid``. A
    :class:`~repro.core.faults.FaultPlan` replaces this with its drawn
    ``draw_rowblock_batch`` counts (a dropped machine is a missing
    summand — count 0)."""
    b = channel.block_rows(n_pad)
    nv = jnp.asarray(n_pad if n_valid is None else n_valid, jnp.int32)
    blocks = jnp.arange(channel.machines, dtype=jnp.int32)
    return jnp.clip(nv - blocks * b, 0, b)


def mac_sign_codes(
    x: jax.Array,
    strategy: Strategy,
    *,
    n_valid: jax.Array | int | None = None,
    delivered: jax.Array | None = None,
    flip: jax.Array | None = None,
) -> jax.Array:
    """Encode stage of the MAC plane: raw (..., n, d) samples -> the ±1
    int8 sign codes each machine CONTRACTS LOCALLY before transmitting
    its partial Gram into the superposition. Rows a machine did not
    deliver (pad rows, or a ``delivered`` fault realization's dropped /
    truncated blocks) are zeroed — they superpose to nothing, exactly the
    missing-summand semantics of the channel. In the lossless case the
    keep mask reduces to the plain valid-sample prefix, so the masked
    codes are BIT-IDENTICAL to the gather sign payload.

    ``delivered`` is the (..., machines) per-block delivered-row count
    (defaults to :func:`mac_delivered_rows`); ``flip`` threads the fault
    plane's sign bit flips exactly as on the gather wire.
    """
    from .quantizers import sign_codes

    ch = strategy.channel
    n_pad = x.shape[-2]
    b = ch.block_rows(n_pad)
    u = sign_codes(x)
    if flip is not None:
        u = jnp.where(flip, jnp.negative(u), u)
    if delivered is None:
        delivered = mac_delivered_rows(ch, n_pad, n_valid)
    offs = jnp.arange(n_pad, dtype=jnp.int32) % b   # offset within block
    blk = jnp.arange(n_pad, dtype=jnp.int32) // b   # owning machine
    keep = offs < jnp.asarray(delivered, jnp.int32)[..., blk]
    return jnp.where(keep[..., :, None], u, jnp.int8(0))


def mac_effective_count(
    strategy: Strategy,
    n_pad: int,
    *,
    n_valid: jax.Array | int | None = None,
    delivered: jax.Array | None = None,
) -> jax.Array:
    """Total sample count inside the superposed statistic: the sum of the
    delivered block rows ((...,) f32 — exactly ``n_valid`` lossless;
    smaller when a fault realization dropped summands)."""
    if delivered is None:
        delivered = mac_delivered_rows(strategy.channel, n_pad, n_valid)
    return jnp.sum(jnp.asarray(delivered, jnp.int32), axis=-1).astype(
        jnp.float32)


def mac_estimate(
    gram: jax.Array,
    strategy: Strategy,
    n_eff: jax.Array,
    *,
    corr: bool = False,
) -> jax.Array:
    """Central estimate from the SUPERPOSED sum statistic — the sum of
    per-machine partial sign Grams is numerically THE masked Gram (f32
    integer addition is exact), so the center only needs the effective
    count ``n_eff`` ((...,) — it never sees per-machine payloads) fed
    through the shared estimate tails' per-entry path: degenerate trials
    (count < 2, e.g. every machine dropped) neutralize exactly like the
    fault plane's voided entries."""
    n = jnp.asarray(n_eff, jnp.float32)[..., None, None]
    tail = corr_from_gram if corr else weights_from_gram
    return tail(gram, n, strategy)


def mac_weights_batch(
    x: jax.Array,
    strategy: Strategy,
    *,
    n_valid: jax.Array | int | None = None,
    delivered: jax.Array | None = None,
    flip: jax.Array | None = None,
    engine: GramEngine | None = None,
    corr: bool = False,
) -> jax.Array:
    """Single-process MAC reference path: encode+mask, contract the full
    masked codes in one launch (== the superposition of every machine's
    partial Gram, exactly), estimate from the effective count. The mesh
    runtime computes per-rank partial Grams and ``superposed_psum``-s
    them instead; integer exactness makes both bit-identical."""
    u = mac_sign_codes(x, strategy, n_valid=n_valid, delivered=delivered,
                       flip=flip)
    eng = resolve_engine(engine)
    gram = (eng.gram_batch if u.ndim == 3 else eng.gram)(u)
    n_eff = mac_effective_count(strategy, x.shape[-2], n_valid=n_valid,
                                delivered=delivered)
    return mac_estimate(gram, strategy, n_eff, corr=corr)


def budget_centroid_table(cap: int) -> np.ndarray:
    """Host (cap+1, 2^cap) f32 PADDED codebook table for mixed-rate
    decode: row r holds ``PerSymbolQuantizer(r)``'s centroids (zero-
    padded), row 0 is all zeros (a silent machine decodes to nothing).
    Concrete numpy on purpose — it is baked into the trace as a constant,
    like the single-rate path's ``centroids_np``."""
    from .quantizers import PerSymbolQuantizer

    tbl = np.zeros((cap + 1, 1 << cap), np.float32)
    for r in range(1, cap + 1):
        cb = PerSymbolQuantizer(r).centroids_np
        tbl[r, : cb.shape[0]] = cb
    return tbl


def budget_payload(
    x: jax.Array,
    strategy: Strategy,
    rates: jax.Array,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
) -> jax.Array:
    """Encode stage of the budget plane: raw (..., n, d) samples + the
    (d,) per-FEATURE rate vector (``BudgetChannel.column_rates``, a
    TRACED operand so one compiled sweep serves every allocation) ->
    mixed-rate int8 bin codes. Each column is encoded at its own rate by
    a static select over rates 1..cap (the strategy's ``rate`` is the
    cap); rate-0 columns (machines whose budget ran out) and undelivered
    rows carry ``MASKED_CODE``. Columnwise + rowwise ops only, so a
    feature-sliced encode followed by a gather reassembles the full
    payload bit-for-bit — the mesh-parity property of the gather wire,
    inherited.
    """
    from .quantizers import (MASKED_CODE, PerSymbolQuantizer, valid_row_mask,
                             valid_sample_mask)

    n_pad = x.shape[-2]
    rates = jnp.asarray(rates, jnp.int32)
    out = jnp.full(x.shape, MASKED_CODE, jnp.int8)
    for r in range(1, strategy.rate + 1):
        out = jnp.where(rates == r,
                        PerSymbolQuantizer(r).encode(x).astype(jnp.int8), out)
    if n_rows is not None:
        mask = valid_row_mask(n_pad, n_rows)
    elif n_valid is not None:
        mask = valid_sample_mask(n_pad, n_valid)[:, None]
    else:
        return out
    return jnp.where(mask, out, jnp.int8(MASKED_CODE))


def budget_operand(
    codes: jax.Array,
    strategy: Strategy,
    rates: jax.Array,
) -> jax.Array:
    """Mixed-rate decode at the center: int8 codes + (d,) rates -> f32
    centroid values through the padded table (``tbl[rates, codes]``),
    with ``MASKED_CODE`` entries restored to 0 so they contract to
    nothing. The per-rate codebooks differ, so the single-codebook
    ``code_gram`` kernel path does not apply — the decoded f32 operand
    goes through the plain Gram."""
    from .quantizers import MASKED_CODE

    cap = strategy.rate
    tbl = jnp.asarray(budget_centroid_table(cap))
    r = jnp.clip(jnp.asarray(rates, jnp.int32), 0, cap)
    vals = tbl[r, jnp.maximum(codes, 0).astype(jnp.int32)]
    return jnp.where(codes == jnp.int8(MASKED_CODE), 0.0, vals)


def budget_counts(
    rates: jax.Array,
    n_pad: int,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
) -> jax.Array:
    """(..., d, d) effective pairwise counts under the rate allocation:
    a rate-0 column delivered nothing, so its count is 0 and the shared
    estimate tails neutralize its entries (weight 0 / identity) — the
    same graceful degradation as a dropped machine. Composes with a
    fault realization's per-feature ``n_rows`` counts."""
    rates = jnp.asarray(rates, jnp.int32)
    if n_rows is not None:
        n_col = jnp.asarray(n_rows, jnp.int32)
    else:
        nv = n_pad if n_valid is None else n_valid
        n_col = jnp.asarray(nv, jnp.int32) * jnp.ones_like(rates)
    return effective_counts(jnp.where(rates > 0, n_col, 0))


def budget_estimate(
    codes: jax.Array,
    strategy: Strategy,
    rates: jax.Array,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
    engine: GramEngine | None = None,
    corr: bool = False,
) -> jax.Array:
    """Central contraction + estimate from the (gathered) mixed-rate
    payload: decode through :func:`budget_operand`, Gram through the
    engine, normalize by :func:`budget_counts`."""
    vals = budget_operand(codes, strategy, rates)
    eng = resolve_engine(engine)
    gram = (eng.gram_batch if vals.ndim == 3 else eng.gram)(vals)
    n = budget_counts(rates, codes.shape[-2], n_valid=n_valid, n_rows=n_rows)
    tail = corr_from_gram if corr else weights_from_gram
    return tail(gram, n, strategy)


def budget_weights_batch(
    x: jax.Array,
    strategy: Strategy,
    rates: jax.Array,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
    engine: GramEngine | None = None,
    corr: bool = False,
) -> jax.Array:
    """Single-process budget reference path: mixed-rate encode -> decode
    -> Gram -> estimate (the mesh runtime encodes feature slices and
    gathers the int8 codes through the channel first; the encode commutes
    with slicing, so both agree bit-for-bit)."""
    codes = budget_payload(x, strategy, rates, n_valid=n_valid,
                           n_rows=n_rows)
    return budget_estimate(codes, strategy, rates, n_valid=n_valid,
                           n_rows=n_rows, engine=engine, corr=corr)


def strategy_corr(
    x: jax.Array,
    strategy: Strategy,
    *,
    engine: GramEngine | None = None,
) -> jax.Array:
    """(n, d) raw samples -> the (d, d) correlation statistic a sparse
    Strategy's glasso solve ingests — the encode -> contract -> estimate
    chain with :func:`corr_from_gram` as the tail (the sparse twin of
    :func:`strategy_weights`)."""
    ch = strategy.channel
    if ch.kind == "mac":
        return mac_weights_batch(x, strategy, engine=engine, corr=True)
    if ch.kind == "budget":
        rates = ch.column_rates(x.shape[0], x.shape[1], strategy.rate)
        return budget_weights_batch(x, strategy, rates, engine=engine,
                                    corr=True)
    payload = strategy_payload(x, strategy)
    gram = payload_gram(payload, strategy, engine=engine)
    return corr_from_gram(gram, x.shape[0], strategy)


def strategy_corr_batch(
    x: jax.Array,
    strategy: Strategy,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
    flip: jax.Array | None = None,
    engine: GramEngine | None = None,
    rates: jax.Array | None = None,
    delivered: jax.Array | None = None,
) -> jax.Array:
    """(t, n, d) stacked raw samples -> (t, d, d) correlation statistics
    for a sparse Strategy: the batched, valid-length-masked form of
    :func:`strategy_corr` used by the sparse trial plane (same bucketing
    semantics as :func:`strategy_weights_batch`; ``n_rows``/``flip``
    thread a fault plan's masks exactly as there, normalizing by the
    per-entry :func:`effective_counts`; ``rates``/``delivered`` dispatch
    the channel plane exactly as there)."""
    ch = strategy.channel
    if ch.kind == "mac":
        return mac_weights_batch(x, strategy, n_valid=n_valid,
                                 delivered=delivered, flip=flip,
                                 engine=engine, corr=True)
    if ch.kind == "budget":
        if rates is None:
            raise ValueError("budget-channel strategies need the (d,) "
                             "per-feature rates operand")
        return budget_weights_batch(x, strategy, rates, n_valid=n_valid,
                                    n_rows=n_rows, engine=engine, corr=True)
    n_pad = x.shape[-2]
    payload = strategy_payload(x, strategy, n_valid=n_valid, n_rows=n_rows,
                               flip=flip)
    gram = payload_gram(payload, strategy, n_valid=n_valid, n_rows=n_rows,
                        engine=engine)
    if n_rows is not None:
        n = effective_counts(n_rows)
    else:
        n = n_pad if n_valid is None else jnp.asarray(n_valid, jnp.float32)
    return corr_from_gram(gram, n, strategy)


def strategy_payload(
    x: jax.Array,
    strategy: Strategy,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
    flip: jax.Array | None = None,
) -> jax.Array:
    """Encode stage: raw (..., n, d) samples -> the strategy's wire payload.

    This is exactly what one of the paper's machines transmits (and what
    :func:`payload_gram` contracts): elementwise per feature column, so a
    feature-sliced call followed by an all-gather reassembles the full
    payload bit-for-bit — the property the distributed trial plane's
    parity gate rests on.

    Layouts (leading batch axes pass through):
      * values / signs / bin codes — sample-major ``(..., n, d)`` (f32 /
        int8 ±1 / int8 in [0, 2^R));
      * packed wires — feature-major ``(..., d, n*R/8)`` uint8
        (``quantizers.pack_codes`` sample-axis layout). Sign payloads pack
        whenever ``strategy.packed_gram_ok(n)``; per-symbol payloads pack
        when ``(8 // rate) | n`` (else they fall back to int8 codes).

    ``n_valid`` (may be traced) masks pad rows: values/signs to 0, bin
    codes to ``quantizers.MASKED_CODE`` (packed wires carry pad symbols as
    0 bits — :func:`payload_operand` restores the sentinel at the center).

    ``n_rows`` — the (..., d) per-FEATURE delivered-row counts a
    :class:`~repro.core.faults.FaultPlan` draws — generalizes ``n_valid``
    to the fault plane: each feature column is prefix-masked to its own
    count (0 for a dropped machine's features, a truncated prefix for a
    straggler's), and wins over ``n_valid`` when both are given (fault
    counts are already clamped to the valid length). ``flip`` is the
    (..., n, d) bit-flip corruption mask: sign-method payloads flip the
    affected sign bits (a flipped bit is still a valid symbol — the 1-bit
    wire's natural corruption model); per-symbol and float wires carry no
    single-bit semantics and ignore it.
    """
    from .quantizers import (MASKED_CODE, PerSymbolQuantizer, pack_codes,
                             sign_codes, valid_row_mask, valid_sample_mask)

    n_pad = x.shape[-2]
    mask = None
    if n_rows is not None:
        mask = valid_row_mask(n_pad, n_rows)               # (..., n, d)
    elif n_valid is not None:
        mask = valid_sample_mask(n_pad, n_valid)[:, None]  # (n, 1)

    if strategy.method == "original":
        return x if mask is None else jnp.where(mask, x, 0.0)
    if strategy.method == "sign":
        if strategy.packed_gram_ok(n_pad):
            bits = x >= 0
            if flip is not None:
                bits ^= flip
            if mask is not None:
                bits &= mask
            return pack_codes(
                jnp.swapaxes(bits.astype(jnp.int8), -2, -1), 1)  # (., d, n/8)
        u = sign_codes(x)
        if flip is not None:
            u = jnp.where(flip, jnp.negative(u), u)
        return u if mask is None else jnp.where(mask, u, jnp.int8(0))
    q = PerSymbolQuantizer(strategy.rate)
    codes = q.encode(x).astype(jnp.int8)
    if strategy.wire == "packed" and n_pad % (8 // strategy.rate) == 0:
        # dense R-bit wire: pad symbols travel as code 0 (any valid code —
        # the center re-masks them from n_valid/n_rows before contracting)
        if mask is not None:
            codes = jnp.where(mask, codes, jnp.int8(0))
        return pack_codes(
            jnp.swapaxes(codes, -2, -1), strategy.rate)  # (., d, n*R/8)
    if mask is not None:
        codes = jnp.where(mask, codes, jnp.int8(MASKED_CODE))
    return codes


def payload_operand(
    payload: jax.Array,
    strategy: Strategy,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
) -> jax.Array:
    """Wire payload -> the Gram operand the engine kernels ingest.

    Identity for every format the engine contracts natively (values, ±1
    signs, bin codes, 1-bit packed signs). Only the per-symbol packed wire
    needs work: unpack the dense R-bit bytes back to int8 bin codes
    (feature-major -> sample-major) and restore the ``MASKED_CODE``
    sentinel on pad rows — integer-exact, so the operand equals the
    un-packed codes entry for entry.

    Under per-feature ``n_rows`` fault counts the 1-bit PACKED sign wire
    is unpacked too: the popcount identity's uniform shift
    (``G = n - 2*popcount``) assumes every feature shares one prefix
    length, which heterogeneous dropout/straggling breaks — so the bytes
    are expanded to ±1 int8 signs with undelivered rows zeroed, which the
    integer-exact Gram contracts to the same values the popcount path
    yields whenever the counts ARE uniform (the zero-fault bit-identity).
    """
    from .quantizers import (MASKED_CODE, unpack_codes, valid_row_mask,
                             valid_sample_mask)

    if payload.dtype != jnp.uint8:
        return payload
    if strategy.method == "sign":
        if n_rows is None:
            return payload  # the popcount path contracts the bytes directly
        bits = jnp.swapaxes(unpack_codes(payload, 1), -2, -1)
        u = jnp.where(bits > 0, jnp.int8(1), jnp.int8(-1))
        return jnp.where(valid_row_mask(u.shape[-2], n_rows),
                         u, jnp.int8(0))
    if strategy.method != "persymbol":
        return payload
    codes = jnp.swapaxes(
        unpack_codes(payload, strategy.rate), -2, -1).astype(jnp.int8)
    if n_rows is not None:
        codes = jnp.where(valid_row_mask(codes.shape[-2], n_rows),
                          codes, jnp.int8(MASKED_CODE))
    elif n_valid is not None:
        mask = valid_sample_mask(codes.shape[-2], n_valid)[:, None]
        codes = jnp.where(mask, codes, jnp.int8(MASKED_CODE))
    return codes


def payload_gram(
    payload: jax.Array,
    strategy: Strategy,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
    payload_rows: jax.Array | None = None,
    n_rows_rows: jax.Array | None = None,
    engine: GramEngine | None = None,
) -> jax.Array:
    """Central contraction: (gathered) wire payload -> (..., d, d) Gram.

    Dispatches through the engine's batched entry points when the payload
    carries a leading batch axis (the trial plane's trial dimension — one
    kernel launch for the whole batch on pallas). 1-bit packed sign
    payloads are contracted DIRECTLY (the packed sign Gram on the wire bytes);
    everything else goes through :func:`payload_operand` first.

    ``payload_rows`` (a feature-slice payload of the same format) selects
    the rowblock placement: the result is the rectangular
    ``(..., d_rows, d)`` Gram block of those rows against the full
    payload. ``n_valid`` applies the integer-exact masked-count shift to
    the packed sign identity (``G = n_valid - 2*popcount``).

    ``n_rows`` / ``n_rows_rows`` thread the fault plane's per-feature
    delivered-row counts for the full payload and (under rowblock) for
    the row-slice payload respectively: the packed sign fast path is
    bypassed (its uniform shift is invalid under heterogeneous prefixes —
    see :func:`payload_operand`) and every operand is prefix-masked per
    feature, so each Gram entry sums exactly its
    ``effective_counts(n_rows)`` surviving rows.
    """
    eng = resolve_engine(engine)
    batched = payload.ndim == 3

    if (strategy.method == "sign" and payload.dtype == jnp.uint8
            and n_rows is None):
        n_pad = payload.shape[-1] * 8
        fn = eng.packed_sign_gram_batch if batched else eng.packed_sign_gram
        if payload_rows is not None:
            gram = fn(payload_rows, n_pad, payload)
        else:
            gram = fn(payload, n_pad)
        if n_valid is not None:
            # pad bits are 0 in every row, so they xor away and the
            # kernel's n_pad - 2*popcount only needs the integer-exact
            # shift to the true count: G_valid = n_valid - 2*popcount
            gram = gram - (n_pad - jnp.asarray(n_valid, jnp.float32))
        return gram

    u = payload_operand(payload, strategy, n_valid=n_valid, n_rows=n_rows)
    rows = None
    if payload_rows is not None:
        rows = payload_operand(payload_rows, strategy, n_valid=n_valid,
                               n_rows=n_rows_rows)
    if strategy.method == "persymbol":
        from .quantizers import PerSymbolQuantizer

        # the CONCRETE codebook: this runs under jit (the trial plane's
        # stage traces), where the quantizer's jax-array centroids are
        # tracers and would skip the engine's integer-exact rate-1 dispatch
        cb = PerSymbolQuantizer(strategy.rate).centroids_np
        fn = eng.code_gram_batch if batched else eng.code_gram
        if rows is not None:
            return fn(rows, cb, u)
        return fn(u, cb)
    fn = eng.gram_batch if batched else eng.gram
    return fn(u if rows is None else rows, u if rows is not None else None)


def strategy_weights(
    x: jax.Array,
    strategy: Strategy,
    *,
    engine: GramEngine | None = None,
) -> jax.Array:
    """(n, d) raw samples -> (d, d) Chow-Liu weight matrix for a Strategy.

    The single declarative entry point over the per-method estimators —
    the encode -> contract -> estimate stage chain
    (:func:`strategy_payload` -> :func:`payload_gram` ->
    :func:`weights_from_gram`) on one unbatched dataset. Pure and jit-able
    with ``strategy`` as a trace-time constant. Non-gather channels
    dispatch to their planes (the budget allocation is derived from the
    static sample count here — pass explicit ``rates`` through the batch
    entry point for bucketed sweeps).

    On the gather channel the three stages are the spans
    ``repro.structure.encode``, ``.gram`` and ``.weights``: eagerly they
    cover each stage's dispatch; under ``jit`` they record trace time only.
    """
    ch = strategy.channel
    if ch.kind == "mac":
        return mac_weights_batch(x, strategy, engine=engine)
    if ch.kind == "budget":
        rates = ch.column_rates(x.shape[0], x.shape[1], strategy.rate)
        return budget_weights_batch(x, strategy, rates, engine=engine)
    with span("structure.encode"):
        payload = strategy_payload(x, strategy)
    with span("structure.gram"):
        gram = payload_gram(payload, strategy, engine=engine)
    with span("structure.weights"):
        return weights_from_gram(gram, x.shape[0], strategy)


def strategy_weights_batch(
    x: jax.Array,
    strategy: Strategy,
    *,
    n_valid: jax.Array | int | None = None,
    n_rows: jax.Array | None = None,
    flip: jax.Array | None = None,
    engine: GramEngine | None = None,
    rates: jax.Array | None = None,
    delivered: jax.Array | None = None,
) -> jax.Array:
    """(t, n, d) stacked raw samples -> (t, d, d) Chow-Liu weights.

    The batched, valid-length-masked form of :func:`strategy_weights` used
    by the one-launch sweep engine (``experiments.run_trials``): the same
    stage chain, with the trial axis going through the Gram engine's
    ``*_batch`` entry points (a native kernel grid dimension on pallas,
    one batched einsum on xla) instead of ``vmap``-of-estimator.

    ``n_valid`` (may be a TRACED scalar) enables shape bucketing: rows
    >= n_valid are padding, masked inside :func:`strategy_payload` so
    every pad row contributes exactly 0 to the Gram and all sample-count
    normalizations use n_valid. For the integer-exact sign paths (int8 and
    packed) the masked statistics are BIT-EQUAL to the unpadded ones;
    float paths agree to accumulation-order rounding, which preserves the
    weight rank order (all Boruvka needs) in every non-adversarial case.

    ``n_rows`` / ``flip`` thread a :class:`~repro.core.faults.FaultPlan`
    realization (per-feature delivered-row counts + sign bit flips): the
    Gram is prefix-masked per feature and the weights normalize by the
    per-entry :func:`effective_counts` with voided entries neutralized to
    weight 0 — the graceful-degradation path. A zero-fault realization
    (all counts == n_valid, ``flip=None``) is bit-identical to the
    faultless call.

    ``rates`` / ``delivered`` are the channel plane's operands —
    respectively the (d,) per-feature rate vector a
    :class:`~repro.comm.channel.BudgetChannel` strategy encodes with, and
    the (t, machines) delivered-row counts a fault plan draws for a
    :class:`~repro.comm.channel.MACChannel` strategy. The gather channel
    (the default) ignores both, and its body below is TEXTUALLY the
    pre-channel code: gather sweeps trace bit-identically to the
    pre-refactor engine by construction.
    """
    ch = strategy.channel
    if ch.kind == "mac":
        return mac_weights_batch(x, strategy, n_valid=n_valid,
                                 delivered=delivered, flip=flip,
                                 engine=engine)
    if ch.kind == "budget":
        if rates is None:
            raise ValueError("budget-channel strategies need the (d,) "
                             "per-feature rates operand")
        return budget_weights_batch(x, strategy, rates, n_valid=n_valid,
                                    n_rows=n_rows, engine=engine)
    t, n_pad, d = x.shape
    payload = strategy_payload(x, strategy, n_valid=n_valid, n_rows=n_rows,
                               flip=flip)
    gram = payload_gram(payload, strategy, n_valid=n_valid, n_rows=n_rows,
                        engine=engine)
    if n_rows is not None:
        n = effective_counts(n_rows)
    else:
        n = n_pad if n_valid is None else jnp.asarray(n_valid, jnp.float32)
    return weights_from_gram(gram, n, strategy)
