"""Graphical lasso over (quantized) data — the paper's stated extension.

The paper's conclusion (§7): "the tree structure can be generalized to
sparse structures where sparse learning methods such as glasso over the
quantized data might be crucial." This module implements that extension:

    minimize_Theta  -logdet(Theta) + tr(S Theta) + lambda * ||Theta||_1,off

solved by proximal gradient (ISTA) with a monotone step guard: the fixed
step 1/L estimated from the eigenvalues of S is only an upper-bound guess
(the true curvature on the iterate path is 1/eigmin(Theta)^2), so each
iteration evaluates the objective of the candidate and halves the step
instead of accepting an increase — the objective sequence is
non-increasing by construction, even on ill-conditioned inputs. The whole
solve is pure `jax.lax` (fori_loop + eigendecompositions — d is
feature-count-sized, not token-sized), so :func:`glasso_batch` vmaps it
over a stacked (b, d, d) batch of Grams: the sparse trial plane
(``experiments.run_trials``) solves a whole Monte-Carlo sweep point in ONE
fused launch.

The input S may be the sample covariance of ORIGINAL data, of PER-SYMBOL
QUANTIZED data (eq. 32), or the arcsine-inverted SIGN correlation (eq. 3
inverted) — the point of the extension is that few-bit S still recovers
the sparse support. The sign-implied S is an elementwise `sin` transform
of a sample statistic and is NOT guaranteed PSD at small n;
:func:`nearest_correlation` eigen-clips it back to a valid correlation
matrix before the solve (the `-logdet` objective and the `inv` init blow
up on indefinite inputs otherwise).

Support recovery thresholds the NORMALIZED partial correlations
|Theta_jk| / sqrt(Theta_jj * Theta_kk) — scale-free, unlike raw
|Theta_jk| whose magnitude varies with lam and conditioning.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

#: f32 matmuls run at full precision: the TPU's default rounds operands
#: to bf16
_HI = jax.lax.Precision.HIGHEST

#: default ISTA iteration budget shared by every glasso entry point (the
#: trial plane, the wire runtime and the host helpers key their jit caches
#: on it, so one number keeps them on one compiled solver).
DEFAULT_STEPS = 500

#: default partial-correlation support threshold, shared by every entry
#: point that recovers a support (:func:`support`,
#: :func:`learn_sparse_structure`, the trial plane's
#: ``TrialPlan.glasso_tol``, ``experiments.learned_adjacency`` and
#: ``distributed.distributed_learn_structure``) so the same data +
#: strategy yields the same graph whichever door it enters through. The
#: eigenvalue-floor PSD projection refills soft-thresholded zeros with
#: small nonzeros, so the cutoff must sit well above that noise floor.
SUPPORT_TOL = 0.05


def soft_threshold(x: jax.Array, t) -> jax.Array:
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def nearest_correlation(S: jax.Array, *, eps: float = 1e-4) -> jax.Array:
    """Project a symmetric matrix to a nearby valid correlation matrix.

    Eigen-clip to eigenvalues >= ``eps`` then renormalize the diagonal to
    1. Identity (up to f32 round-off) on inputs that are already
    correlation matrices with eigmin >= eps; the repair path exists for
    the sign method's arcsine-inverted statistic, whose elementwise `sin`
    transform can leave the sample matrix indefinite at small n. Batched
    over leading axes, jit-able.
    """
    S = jnp.asarray(S, jnp.float32)
    S = (S + jnp.swapaxes(S, -1, -2)) / 2.0
    w, v = jnp.linalg.eigh(S)
    w = jnp.maximum(w, eps)
    S = jnp.einsum("...ij,...j,...kj->...ik", v, w, v, precision=_HI)
    dinv = 1.0 / jnp.sqrt(jnp.diagonal(S, axis1=-2, axis2=-1))
    S = S * dinv[..., :, None] * dinv[..., None, :]
    return (S + jnp.swapaxes(S, -1, -2)) / 2.0


def _objective(w_theta, theta, S, lam, off):
    """-logdet + tr(S Theta) + lam*||Theta||_1,off from the iterate's
    eigenvalues (already floored, so the logdet is finite)."""
    return (-jnp.sum(jnp.log(w_theta))
            + jnp.sum(S * theta)
            + lam * jnp.sum(jnp.where(off, jnp.abs(theta), 0.0)))


def _carry_init(S: jax.Array, lam: jax.Array, step_scale: float, eps: float):
    """Shared ISTA start point for :func:`_glasso_run`.

    Init Theta0 = inv(S + 0.5 I) through the eigendecomposition (floored
    so the init is PSD and its logdet finite even on an un-repaired
    indefinite S), and a step guess from the initial conditioning: the
    gradient of -logdet(Theta) + tr(S Theta) is S - Theta^{-1}, whose
    curvature on the iterate path is bounded by 1/eigmin(Theta)^2 — the
    guess can overshoot, which is what the halve-on-increase guard in the
    run loop repairs. ``eta0`` depends only on S, so the path engine
    reuses it across every lam of a grid.
    """
    d = S.shape[0]
    off = ~jnp.eye(d, dtype=bool)
    ws, v0 = jnp.linalg.eigh(S + 0.5 * jnp.eye(d))
    w0 = jnp.maximum(1.0 / jnp.maximum(ws, eps), eps)
    theta0 = jnp.matmul(v0 * w0, v0.T, precision=_HI)
    eta0 = step_scale * (1.0 / jnp.linalg.norm(S + jnp.eye(d), 2)) ** 2
    obj0 = _objective(w0, theta0, S, lam, off)
    return theta0, w0, v0, eta0, obj0


def _glasso_run(
    theta: jax.Array, w: jax.Array, v: jax.Array, eta, obj,
    S: jax.Array, lam: jax.Array, n_steps: int, eps: float,
    conv_tol: float = 0.0, active=None,
):
    """Masked monotone-ISTA run from a given iterate (theta, w, v).

    The iterate travels as (theta, w, v) with theta == (v * w) @ v.T:
    the gradient's Theta^{-1} is reconstructed from the carried
    eigendecomposition ((v / w) @ v.T) instead of an LU inverse —
    cheaper, and bit-stable under batching (jnp.linalg.inv is the one
    primitive whose low-order bits vary with the vmapped batch size,
    which would break the trial plane's 1-vs-N-device parity gate).

    The ``fori_loop`` of the original solver is now a ``while``-style step
    budget: the loop runs until ``n_steps`` OR until the solve converges
    (an ACCEPTED step moved theta by at most ``conv_tol`` in max-abs — a
    REJECTED step leaves theta unchanged and must not count as
    convergence). Once converged the whole carry is frozen, so an early
    exit is bit-identical to running the loop to any larger budget.
    ``conv_tol=0.0`` never converges and reproduces the fixed-budget
    solver exactly. ``active=False`` marks a lane (a pow2/chunk pad slot)
    done before step 0, so padding stops burning solver iterations.

    Returns ``(theta, w, v, iters)`` with ``iters`` the number of loop
    steps actually spent (early-exit telemetry; pads report 0).
    """
    d = S.shape[0]
    off = ~jnp.eye(d, dtype=bool)
    done0 = jnp.asarray(False) if active is None else jnp.logical_not(active)

    def cond(carry):
        _, _, _, _, _, it, done = carry
        return jnp.logical_and(it < n_steps, jnp.logical_not(done))

    def body(carry):
        theta, w, v, eta, obj, it, done = carry
        g = S - jnp.matmul(v / w, v.T, precision=_HI)
        z = theta - eta * g
        z = jnp.where(off, soft_threshold(z, eta * lam), z)
        z = (z + z.T) / 2.0
        # PSD projection with an eigenvalue floor (keeps logdet finite)
        wz, vz = jnp.linalg.eigh(z)
        wz = jnp.maximum(wz, eps)
        z = jnp.matmul(vz * wz, vz.T, precision=_HI)
        obj_z = _objective(wz, z, S, lam, off)
        # monotone guard: a candidate that increases the objective means
        # the step overshot the local curvature — reject it and halve eta
        # (float-noise slack so a converged iterate is not rejected)
        ok = obj_z <= obj + 1e-6
        upd = jnp.logical_and(ok, jnp.logical_not(done))
        # the convergence delta compares the accepted candidate against
        # the iterate it replaces, BEFORE the selects overwrite theta
        if conv_tol > 0.0:
            conv = jnp.logical_and(
                upd, jnp.max(jnp.abs(z - theta)) <= conv_tol)
        else:
            conv = jnp.asarray(False)
        theta = jnp.where(upd, z, theta)
        w = jnp.where(upd, wz, w)
        v = jnp.where(upd, vz, v)
        obj = jnp.where(upd, obj_z, obj)
        eta = jnp.where(done, eta, jnp.where(ok, eta, eta / 2.0))
        it = it + jnp.where(done, 0, 1)
        done = jnp.logical_or(done, conv)
        return theta, w, v, eta, obj, it, done

    theta, w, v, _, _, iters, _ = jax.lax.while_loop(
        cond, body,
        (theta, w, v, eta, obj, jnp.asarray(0, jnp.int32), done0))
    return theta, w, v, iters


def _glasso_solve(
    S: jax.Array, lam: jax.Array, n_steps: int, step_scale: float,
    eps: float, conv_tol: float = 0.0, active=None,
) -> jax.Array:
    """One (d, d) monotone ISTA solve (trace body of glasso/glasso_batch)."""
    S = (S + S.T) / 2.0
    theta0, w0, v0, eta0, obj0 = _carry_init(S, lam, step_scale, eps)
    theta, _, _, _ = _glasso_run(
        theta0, w0, v0, eta0, obj0, S, lam, n_steps, eps, conv_tol, active)
    return theta


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "step_scale", "eps",
                                    "conv_tol"))
def glasso(
    S: jax.Array,
    lam: float,
    *,
    n_steps: int = DEFAULT_STEPS,
    step_scale: float = 0.9,
    eps: float = 1e-4,
    conv_tol: float = 0.0,
) -> jax.Array:
    """Monotone proximal-gradient graphical lasso.

    Args:
      S: (d, d) sample covariance (unit-diagonal correlation matrices are
        the paper's normalization).
      lam: l1 penalty on off-diagonal entries.
      conv_tol: early-exit threshold — stop once an accepted step moves
        theta by at most this much (max-abs). 0.0 (the default) runs the
        full ``n_steps`` budget exactly as before. Convergence freezes
        the carry, so an early exit is bit-identical to a larger budget.
    Returns:
      (d, d) sparse precision estimate Theta (symmetric PSD). The
      objective sequence is non-increasing (each step's candidate is
      evaluated and the step halved instead of accepting an increase), so
      the solve cannot diverge on ill-conditioned inputs where the fixed
      1/L guess overshoots.
    """
    return _glasso_solve(
        jnp.asarray(S, jnp.float32), jnp.asarray(lam, jnp.float32),
        n_steps, step_scale, eps, conv_tol)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "step_scale", "eps",
                                    "conv_tol", "chunk"))
def glasso_batch(
    S: jax.Array,
    lam,
    *,
    n_steps: int = DEFAULT_STEPS,
    step_scale: float = 0.9,
    eps: float = 1e-4,
    conv_tol: float = 0.0,
    chunk: int | None = None,
) -> jax.Array:
    """Batched, fully device-resident glasso: (b, d, d) Grams -> (b, d, d)
    precision estimates in ONE fused launch.

    ``lam`` may be a scalar or a (b,)-broadcastable array (the sparse
    trial plane stacks strategies with different penalties into one
    batch). This is the solve stage of ``experiments.run_trials`` for
    sparse plans: the whole (S*reps, d, d) sweep point runs as one vmapped
    while-loop, metric sums stay on device, ``host_syncs == 1``.

    ``chunk`` streams the batch through ``lax.map`` in ``chunk``-sized
    vmapped slabs instead of one full vmap: the solver's per-trial
    transients (eigh workspace + carried iterates, ~8 (d, d) f32 planes)
    then scale with ``chunk``, not b — the memory-budgeted solve stage at
    large d. Solves are independent and the iterate path is inv-free
    (bit-stable across batch sizes, see ``_glasso_run``), so chunking
    does not change results; the batch zero-pads to a chunk multiple and
    the pad is sliced off. Pad slots enter the solver with
    ``active=False`` — marked converged before step 0 — so padding burns
    no solver iterations (an all-pad slab exits its while-loop
    immediately) and real slots stay bit-identical (their lanes never
    observe the mask; see ``test_tiling.test_glasso_batch_chunk_parity``).
    """
    S = jnp.asarray(S, jnp.float32)
    lam = jnp.broadcast_to(
        jnp.asarray(lam, jnp.float32), S.shape[:-2])
    b = S.shape[0]
    if chunk is None or chunk >= b:
        solve = jax.vmap(
            lambda s, l: _glasso_solve(s, l, n_steps, step_scale, eps,
                                       conv_tol))
        return solve(S, lam)
    chunk = max(1, chunk)
    pad = (-b) % chunk
    Sp = jnp.pad(S, ((0, pad), (0, 0), (0, 0)))
    lp = jnp.pad(lam, (0, pad), constant_values=1.0)
    act = jnp.arange(b + pad) < b
    d = S.shape[-1]
    solve = jax.vmap(
        lambda s, l, a: _glasso_solve(s, l, n_steps, step_scale, eps,
                                      conv_tol, a))
    theta = jax.lax.map(
        lambda args: solve(*args),
        (Sp.reshape(-1, chunk, d, d), lp.reshape(-1, chunk),
         act.reshape(-1, chunk)))
    return theta.reshape(-1, d, d)[:b]


def glasso_objective(theta: jax.Array, S: jax.Array, lam: float) -> jax.Array:
    """-logdet(Theta) + tr(S Theta) + lam*||Theta||_1,off — the objective
    the monotone guard enforces (regression-testable from outside)."""
    theta = jnp.asarray(theta, jnp.float32)
    S = jnp.asarray(S, jnp.float32)
    d = theta.shape[-1]
    off = ~jnp.eye(d, dtype=bool)
    sign, logdet = jnp.linalg.slogdet(theta)
    return (-jnp.where(sign > 0, logdet, -jnp.inf)
            + jnp.sum(S * theta, axis=(-2, -1))
            + lam * jnp.sum(jnp.where(off, jnp.abs(theta), 0.0),
                            axis=(-2, -1)))


def partial_correlations(theta: jax.Array) -> jax.Array:
    """Normalized partial correlations |Theta_jk| / sqrt(Theta_jj Theta_kk)
    (diagonal = 1). Scale-free: invariant to D Theta D for any positive
    diagonal D, unlike raw |Theta_jk|. Batched over leading axes."""
    theta = jnp.abs(jnp.asarray(theta))
    dinv = 1.0 / jnp.sqrt(jnp.diagonal(theta, axis1=-2, axis2=-1))
    return theta * dinv[..., :, None] * dinv[..., None, :]


def support_from_theta(theta: jax.Array,
                       tol: float = SUPPORT_TOL) -> jax.Array:
    """Device-side off-diagonal support of a precision estimate: the
    boolean adjacency of partial correlations > ``tol``. Batched over
    leading axes, jit-able — the support stage of the sparse trial plane.
    """
    p = partial_correlations(theta)
    d = p.shape[-1]
    return (p > tol) & ~jnp.eye(d, dtype=bool)


def support(theta: jax.Array, tol: float = SUPPORT_TOL) -> np.ndarray:
    """Off-diagonal support (boolean adjacency) of a precision estimate.

    Thresholds the NORMALIZED partial correlations
    |Theta_jk| / sqrt(Theta_jj * Theta_kk) — scale-free, where the old raw
    |Theta_jk| > tol rule was scale-dependent (Theta's magnitude varies
    with lam and conditioning). Host twin of :func:`support_from_theta`.
    """
    return np.asarray(support_from_theta(jnp.asarray(theta), tol))


def learn_sparse_structure(
    x: jax.Array,
    lam,
    *,
    method: str = "original",
    rate: int = 4,
    tol: float = SUPPORT_TOL,
    n_steps: int = DEFAULT_STEPS,
) -> np.ndarray:
    """End-to-end: (n, d) data -> glasso support, optionally through the
    paper's per-symbol quantizer (the §7 extension).

    Runs the SAME encode -> contract -> estimate stage chain as every
    other pipeline (``estimators.strategy_payload`` -> ``payload_gram`` ->
    ``corr_from_gram``): the sign path inverts the arcsine law (eq. 3) and
    eigen-clips the result back to a valid correlation matrix
    (:func:`nearest_correlation`) before the solve.

    ``lam`` may be:
      * a float >= 0 — a caller-chosen penalty (0 = unpenalized MLE);
      * the string ``"path"`` — solve a warm-started decreasing lambda
        grid (``path.PathPlan()`` defaults: log grid from ``max|S_off|``)
        in one fused launch and return the EBIC-selected support, so no
        penalty needs to be hand-tuned;
      * a ``path.PathPlan`` — same, with a caller-declared grid/selector.
        Must use EBIC selection: StARS needs a subsample batch, which a
        single (n, d) matrix does not provide — use the trial plane
        (``TrialPlan(path=...)``) for stability selection.
    """
    from . import estimators
    from .strategy import Strategy
    from .path import PathPlan, glasso_path_select

    if method not in ("original", "sign", "persymbol"):
        raise ValueError(f"unknown method {method!r}")
    if isinstance(lam, str):
        if lam != "path":
            raise ValueError(
                f"lam must be a float, 'path', or a PathPlan; got {lam!r}")
        lam = PathPlan()
    if isinstance(lam, PathPlan):
        if lam.select != "ebic":
            raise ValueError(
                "learn_sparse_structure path selection must be 'ebic' — "
                "StARS needs a subsample batch (use TrialPlan(path=...))")
        strat = Strategy(method, rate=rate)
        payload = estimators.strategy_payload(x, strat)
        gram = estimators.payload_gram(payload, strat)
        S = estimators.corr_from_gram(gram, x.shape[0], strat)
        theta, _, _ = glasso_path_select(
            S, lam, x.shape[0], n_steps=n_steps, support_tol=tol)
        return support(theta, tol)
    if lam < 0.0:
        raise ValueError(f"lam must be >= 0 (0 = unpenalized MLE), "
                         f"got {lam!r}")
    # the encode/contract/estimate stages only read method/rate/wire, so a
    # plain (tree) Strategy drives them — which keeps lam = 0 (unpenalized
    # solve) a valid input here, where Strategy's sparse axis requires a
    # positive penalty
    strat = Strategy(method, rate=rate)
    payload = estimators.strategy_payload(x, strat)
    gram = estimators.payload_gram(payload, strat)
    S = estimators.corr_from_gram(gram, x.shape[0], strat)
    return support(glasso(S, lam, n_steps=n_steps), tol)


def random_sparse_precision(
    d: int, density: float, rng: np.random.Generator,
    strength: tuple[float, float] = (0.25, 0.45),
) -> np.ndarray:
    """Random sparse, diagonally-dominant precision matrix (valid GGM)."""
    theta = np.zeros((d, d))
    iu = np.triu_indices(d, k=1)
    mask = rng.random(len(iu[0])) < density
    vals = rng.uniform(*strength, size=mask.sum()) * rng.choice(
        [-1.0, 1.0], size=mask.sum())
    theta[iu[0][mask], iu[1][mask]] = vals
    theta = theta + theta.T
    # diagonal dominance => PSD
    np.fill_diagonal(theta, np.abs(theta).sum(axis=1) + 1.0)
    # normalize to unit-variance marginals (paper's Q_jj = 1 convention)
    cov = np.linalg.inv(theta)
    scale = np.sqrt(np.diag(cov))
    cov = cov / scale[:, None] / scale[None, :]
    return np.linalg.inv(cov)
