"""Chow-Liu structure estimation: maximum-weight spanning tree solvers.

Two MWST implementations with identical tie-breaking semantics:

* ``kruskal_mst`` — the paper's choice (§3): host-side numpy, sort edges by
  descending weight and union-find. Reference implementation (a spanning
  forest with the threshold at -inf).
* ``boruvka_mst`` — TPU-native adaptation: Boruvka's algorithm is O(log d)
  rounds of per-component max-reductions, which vectorizes as jnp reductions
  and scatters — jit-able, vmap-able over stacked weight matrices, and
  usable inside ``shard_map`` on device. The Kruskal algorithm is inherently
  sequential (data-dependent union-find), so this is the hardware adaptation
  of the paper's central-machine step.

Both depend only on the ORDER of the weights (as the paper notes for
Kruskal); we make ties well-defined by ranking flattened weights with a
stable sort, so both algorithms agree exactly on any input.

Device vs host flow: with ``backend="boruvka"`` the weight matrix feeds
``boruvka_mst`` directly as a JAX array and the result is the bool
adjacency — nothing bounces through numpy. Converting an adjacency to the
human-facing edge list (:func:`adjacency_to_edges`) is an explicit host
step, taken only at the edge-list API surface.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.spans import span, spanned
from .strategy import Strategy, as_strategy


# --------------------------------------------------------------------------
# Host-side Kruskal (reference; the algorithm named in the paper)
# --------------------------------------------------------------------------

def kruskal_forest(weights: np.ndarray, min_weight: float) -> list[tuple[int, int]]:
    """Maximum-weight spanning FOREST: Kruskal that stops adding edges whose
    weight is below ``min_weight``. With MI weights this is the thresholded
    Chow-Liu forest of Tan-Anandkumar-Willsky (ref. [25] of the paper) —
    the natural estimator when the true graph may be disconnected.

    Ties are broken by smaller row-major flat index (stable sort), matching
    :func:`boruvka_mst`. ``min_weight=-inf`` yields the spanning tree
    (:func:`kruskal_mst`).

    Non-finite entries (NaN / ±inf) are VOIDED edges — the fault plane's
    masked weight matrices carry them where no effective samples survive —
    and are skipped rather than sorted (NaN comparisons would otherwise
    order them arbitrarily and the threshold test could admit them). With
    voided edges present the result may be a forest, exactly like a
    below-threshold cut.
    """
    w = np.asarray(weights, dtype=np.float64)
    d = w.shape[0]
    iu, ju = np.triu_indices(d, k=1)
    vals = w[iu, ju]
    finite = np.isfinite(vals)
    order = np.argsort(-np.where(finite, vals, -np.inf), kind="stable")
    parent = np.arange(d)

    def find(a: int) -> int:
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    edges: list[tuple[int, int]] = []
    for idx in order:
        # the sort key sends every voided edge to the tail, so the first
        # non-finite value ends the scan like a below-threshold weight
        if not finite[idx] or vals[idx] < min_weight:
            break
        j, k = int(iu[idx]), int(ju[idx])
        rj, rk = find(j), find(k)
        if rj != rk:
            parent[rj] = rk
            edges.append((j, k))
            if len(edges) == d - 1:
                break
    return edges


def kruskal_mst(weights: np.ndarray) -> list[tuple[int, int]]:
    """Max-weight spanning tree via Kruskal. ``weights``: symmetric (d, d).

    The no-threshold special case of :func:`kruskal_forest`.
    """
    return kruskal_forest(weights, min_weight=-np.inf)


# --------------------------------------------------------------------------
# Device-side Boruvka (jit-able, fixed shapes)
# --------------------------------------------------------------------------

def _rank_weights(weights: jax.Array) -> jax.Array:
    """Replace weights by distinct integer ranks (order-preserving).

    MWST depends only on the weight order, so ranking is exact. Stable
    argsort breaks ties by flat index; (j,k)/(k,j) ranks are unified by max,
    which preserves inter-value order. Diagonal is forced to rank -1.
    """
    d = weights.shape[0]
    flat = weights.reshape(-1)
    # ties broken by SMALLER flat (row-major) index first — identical to
    # Kruskal's stable descending sort over triu indices
    order = jnp.argsort(-flat, stable=True)
    ranks = jnp.zeros(d * d, jnp.int32).at[order].set(
        jnp.arange(d * d, 0, -1, dtype=jnp.int32))
    r = ranks.reshape(d, d)
    r = jnp.maximum(r, r.T)
    return jnp.where(jnp.eye(d, dtype=bool), -1, r)


@jax.jit
def boruvka_mst(weights: jax.Array) -> jax.Array:
    """Max-weight spanning tree via parallel Boruvka.

    Args:
      weights: symmetric (d, d) edge-weight matrix (diagonal ignored).
    Returns:
      (d, d) bool adjacency of the MWST (symmetric).

    The round body is idempotent once a single component remains, so the
    while_loop batches correctly under ``vmap`` (trials that converge early
    simply coast while the stragglers finish).
    """
    d = weights.shape[0]
    W = _rank_weights(weights)  # distinct int ranks, diag = -1
    n_jump = int(np.ceil(np.log2(max(d, 2)))) + 1

    def round_body(state):
        comp, sel, _ = state
        cross = comp[:, None] != comp[None, :]
        Wm = jnp.where(cross, W, -1)
        best_w = Wm.max(axis=1)                      # (d,) best outgoing rank per node
        best_k = Wm.argmax(axis=1).astype(jnp.int32)
        # per-component champion rank
        seg_best = jax.ops.segment_max(best_w, comp, num_segments=d)  # (d,) by label
        has_edge = seg_best >= 0
        is_best = (best_w == seg_best[comp]) & (best_w >= 0)
        # champion node per component = smallest index among is_best
        node_score = jnp.where(is_best, d - jnp.arange(d, dtype=jnp.int32), 0)
        seg_node = jax.ops.segment_max(node_score, comp, num_segments=d)
        j_star = d - seg_node                        # valid only where has_edge
        valid = has_edge & (seg_node > 0)
        j_sel = jnp.where(valid, j_star, 0).astype(jnp.int32)
        k_sel = jnp.where(valid, best_k[j_sel], 0).astype(jnp.int32)
        sel = sel.at[j_sel, k_sel].max(valid)
        sel = sel.at[k_sel, j_sel].max(valid)
        # merge component labels: parent[max] = min, then pointer-jump
        cj, ck = comp[j_sel], comp[k_sel]
        hi, lo = jnp.maximum(cj, ck), jnp.minimum(cj, ck)
        hi = jnp.where(valid, hi, jnp.arange(d, dtype=jnp.int32))
        lo = jnp.where(valid, lo, jnp.arange(d, dtype=jnp.int32))
        parent = jnp.arange(d, dtype=jnp.int32).at[hi].min(lo)
        parent = jax.lax.fori_loop(0, n_jump, lambda _, p: p[p], parent)
        comp = parent[comp]
        n_comp = jnp.sum(jnp.bincount(comp, length=d) > 0)
        return comp, sel, n_comp

    init = (
        jnp.arange(d, dtype=jnp.int32),
        jnp.zeros((d, d), dtype=bool),
        jnp.asarray(d, dtype=jnp.int32),
    )
    _, sel, _ = jax.lax.while_loop(lambda s: s[2] > 1, round_body, init)
    return sel


@functools.partial(jax.jit, static_argnames=("chunk",))
def boruvka_mst_batch(weights: jax.Array, chunk: int | None = None
                      ) -> jax.Array:
    """Batched :func:`boruvka_mst`: (b, d, d) weights -> (b, d, d) bools.

    ``chunk=None`` is the plain ``vmap`` (one fused launch for the whole
    trial stack). With ``chunk`` set, the batch streams through
    ``lax.map`` in ``chunk``-sized vmapped slabs, so the solver's
    transient working set (the per-trial rank/component scratch) scales
    with ``chunk`` instead of b — the memory-budgeted metrics stage of
    ``experiments.run_trials`` at large d. Trials are independent, so the
    chunked result is bit-identical per trial to the full vmap; the batch
    zero-pads to a chunk multiple (an all-zero weight matrix still runs —
    rank-based, weight values never matter — and is sliced off).
    """
    b = weights.shape[0]
    if chunk is None or chunk >= b:
        return jax.vmap(boruvka_mst)(weights)
    chunk = max(1, chunk)
    pad = (-b) % chunk
    w = jnp.pad(weights, ((0, pad), (0, 0), (0, 0)))
    sel = jax.lax.map(
        jax.vmap(boruvka_mst),
        w.reshape(-1, chunk, *weights.shape[1:]))
    return sel.reshape(-1, *weights.shape[1:])[:b]


def adjacency_to_edges(adj) -> list[tuple[int, int]]:
    """Explicit host step: symmetric bool adjacency -> canonical edge list.

    The read-back (which waits for the device) is one
    ``repro.structure.fetch`` span, the host extraction one
    ``repro.structure.edges`` span."""
    with span("structure.fetch"):
        adj = np.asarray(adj)
    with span("structure.edges"):
        iu, ju = np.nonzero(np.triu(adj, k=1))
        return [(int(a), int(b)) for a, b in zip(iu, ju)]


# --------------------------------------------------------------------------
# Chow-Liu pipelines (paper §3.1): data -> weights -> MWST
# --------------------------------------------------------------------------

def chow_liu(weights, backend: str = "kruskal") -> list[tuple[int, int]]:
    """MWST edges from a pairwise weight matrix."""
    if backend == "kruskal":
        return kruskal_mst(np.asarray(weights))
    elif backend == "boruvka":
        # device solve on the weights as-is; host conversion only at the
        # edge-list API surface
        return adjacency_to_edges(boruvka_mst(jnp.asarray(weights)))
    raise ValueError(f"unknown backend {backend!r}")


def learn_structure_jit(
    x: jax.Array,
    strategy: Strategy = Strategy(),
    engine=None,
) -> jax.Array:
    """End-to-end Chow-Liu that STAYS ON DEVICE: (n, d) samples -> (d, d)
    bool MWST adjacency.

    Pure and jit-able (``strategy``/``engine`` are trace-time constants);
    this is the per-trial unit the experiments engine vmaps. The MWST is
    always the device Boruvka solver — exactly equal to Kruskal by the
    shared rank construction.
    """
    from . import estimators

    return boruvka_mst(estimators.strategy_weights(x, strategy, engine=engine))


@spanned("learn_structure")
def learn_structure(
    x,
    method: str = "sign",
    rate: int = 1,
    backend: str = "kruskal",
    engine=None,
    strategy: Strategy | None = None,
) -> list[tuple[int, int]]:
    """End-to-end centralized Chow-Liu on (n, d) data; returns edge list.

    Accepts either a :class:`~repro.core.strategy.Strategy` (preferred) or
    the legacy loose kwargs:

    method:
      'sign'      — sign method (§4): 1-bit codes, MI of signs (eq. 4).
      'persymbol' — R-bit per-symbol quantization (§5), eq. (30) estimator.
      'original'  — unquantized baseline (centralized Chow-Liu, eq. 1).
    engine: ``repro.core.gram.GramEngine`` the pairwise Gram dispatches
      through (None = process default). Codes feed the Gram backend as int8
      (sign) / int8 bin codes with in-kernel centroid decode (persymbol).

    With ``backend='boruvka'`` (``strategy.mst``) the weights feed the
    device solver directly; only the final edge list crosses to the host.

    Each call is one ``repro.learn_structure`` span; the MWST solve (its
    dispatch, for Boruvka) is one ``repro.structure.mst`` span inside it.
    """
    from . import estimators

    if strategy is None:
        strategy = as_strategy(
            None, method=method,
            rate=max(rate, 1) if method == "persymbol" else 1,
            mst=backend)
    x = jnp.asarray(x)
    w = estimators.strategy_weights(x, strategy, engine=engine)
    if strategy.mst == "boruvka":
        with span("structure.mst"):
            adj = boruvka_mst(w)
        return adjacency_to_edges(adj)
    with span("structure.mst"):
        return kruskal_mst(np.asarray(w))
