"""Chow-Liu structure estimation: maximum-weight spanning tree solvers.

Two MWST implementations with identical tie-breaking semantics:

* ``kruskal_mst`` — the paper's choice (§3): host-side numpy, sort edges by
  descending weight and union-find. Reference implementation (a spanning
  forest with the threshold at -inf).
* ``boruvka_mst`` — TPU-native adaptation: Boruvka's algorithm is O(log d)
  rounds of per-component max-reductions, which vectorize as dense
  compares and reductions (no scatter, gather or sort) — jit-able,
  vmap-able over stacked weight matrices, and usable inside ``shard_map``
  on device. The Kruskal algorithm is inherently sequential
  (data-dependent union-find), so this is the hardware adaptation of the
  paper's central-machine step.

Both depend only on the ORDER of the weights (as the paper notes for
Kruskal); ties are broken by the smaller flat index in both, so the edge
order is strict, the tree unique, and both algorithms agree exactly on
any input.

Device vs host flow: with ``backend="boruvka"`` the weight matrix feeds
``boruvka_mst`` directly as a JAX array and the result is the bool
adjacency — nothing bounces through numpy — or, where only the edge list
is wanted, the solve's compact edge record (``edges=True``). Converting
either to the human-facing edge list (:func:`adjacency_to_edges`) is an
explicit host step, taken only at the edge-list API surface.
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

def _edge_order(weights: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-edge sort keys ``(key, tie)`` of (d, d) weights, built
    elementwise: no sort, no scatter.

    ``key`` is an order-preserving integer image of the weight in the
    forms ``lax.sort`` compares (-0.0 as +0.0; every NaN alike, below
    -inf); ``tie`` is the row-major flat index. Edge {j,k} takes the
    larger of its (j,k) and (k,j) entries: the larger key, on equal keys
    the smaller flat index. So edges rank by descending weight with ties
    broken by SMALLER flat index first — Kruskal's stable descending sort
    over triu indices — and no two edges rank alike.
    """
    w = weights.astype(jnp.float32)
    d = w.shape[0]
    w = jnp.where(w == 0, jnp.zeros_like(w), w)
    bits = jax.lax.bitcast_convert_type(w, jnp.int32)
    info = jnp.iinfo(jnp.int32)
    key = jnp.where(bits < 0, bits ^ info.max, bits)
    key = jnp.where(jnp.isnan(w), info.min + 1, key)   # info.min: no edge
    tie = jnp.arange(d * d, dtype=jnp.int32).reshape(d, d)
    own = (key > key.T) | ((key == key.T) & (tie < tie.T))
    return jnp.where(own, key, key.T), jnp.where(own, tie, tie.T)


@functools.partial(jax.jit, static_argnames=("edges",))
def boruvka_mst(weights: jax.Array, edges: bool = False) -> jax.Array:
    """Max-weight spanning tree via parallel Boruvka.

    Args:
      weights: symmetric (d, d) edge-weight matrix (diagonal ignored).
      edges: static; what the solve hands back (see Returns).
    Returns:
      ``edges=False``: the (d, d) bool adjacency of the MWST (symmetric).
      ``edges=True``: the (R, d) int32 record of the edges the solve chose,
      R = ceil(log2 d): row r holds round r's champion of each node's
      component as a flat tie index ``j * d + k`` of either orientation,
      ``int32.max`` where no round ran. Every tree edge appears in it, and
      nothing else; :func:`adjacency_to_edges` reads it in O(R d) on the
      host where the adjacency costs O(d^2).

    Edges are ordered by :func:`_edge_order`, a strict total order, so the
    tree is unique and equals :func:`kruskal_mst`'s. Each round every
    component picks its best outgoing edge and merges across it. Every
    step is an elementwise compare plus a reduction, so a round costs a
    few O(d^2) passes and lowers to no scatter, gather or sort: on a TPU
    those run element by element. A component's label is one of its
    nodes, so a label indexes per-node vectors through the one-hot
    reduction ``take``. Every component merges each round, so their count
    at least halves and R rounds suffice.

    The round body is idempotent once a single component remains, so the
    while_loop batches correctly under ``vmap`` (trials that converge
    early simply coast while the stragglers finish).
    """
    K, T = _edge_order(weights)
    d = K.shape[0]
    no_key = jnp.iinfo(jnp.int32).min
    no_tie = jnp.iinfo(jnp.int32).max
    node = jnp.arange(d, dtype=jnp.int32)

    def take(v, i):  # v[i[j]] for every j
        return jnp.where(node[:, None] == i, v[:, None], no_tie).min(0)

    def jump(state):
        f, _ = state
        g = take(f, f)
        return g, jnp.any(g != f)

    def champion(comp):
        same = comp[:, None] == comp
        # node j's best edge out of its component, then the component's
        # best over its members: the champion, named by its tie
        best_key = jnp.where(same, no_key, K).max(0)
        best_tie = jnp.where(~same & (K == best_key), T, no_tie).min(0)
        champ_key = jnp.where(same, best_key[:, None], no_key).max(0)
        champ = jnp.where(same & (best_key[:, None] == champ_key),
                          best_tie[:, None], no_tie).min(0)
        return same, champ

    def merge(comp, same, champ):
        ends = (node[:, None] == champ // d) | (node[:, None] == champ % d)
        across = jnp.where(ends & ~same, comp[:, None], no_tie).min(0)
        hook = jnp.where(champ < no_tie, across, comp)
        # components hook to the label across their champion; the two
        # that chose one edge hook to each other, broken at the smaller
        mutual = take(hook, hook) == comp
        f = jnp.where(mutual & (comp < hook), comp, hook)
        comp, _ = jax.lax.while_loop(lambda s: s[1], jump,
                                     (f, jnp.asarray(True)))
        return comp, jnp.sum(comp == node)

    if edges:
        def record_round(state):
            comp, record, _, r = state
            same, champ = champion(comp)
            record = jax.lax.dynamic_update_slice(record, champ[None], (r, 0))
            comp, count = merge(comp, same, champ)
            return comp, record, count, r + 1

        rounds = max((d - 1).bit_length(), 1)   # ceil(log2 d)
        init = (node, jnp.full((rounds, d), no_tie, jnp.int32),
                jnp.asarray(d, jnp.int32), jnp.asarray(0, jnp.int32))
        return jax.lax.while_loop(lambda s: s[2] > 1, record_round, init)[1]

    def round_body(state):
        comp, chosen, _ = state
        same, champ = champion(comp)
        # T[k, j] == champ[j] only at the champion's ends, j inside
        chosen = chosen | (T == champ)
        comp, count = merge(comp, same, champ)
        return comp, chosen, count

    init = (node, jnp.zeros((d, d), dtype=bool), jnp.asarray(d, jnp.int32))
    _, chosen, _ = jax.lax.while_loop(lambda s: s[2] > 1, round_body, init)
    return chosen | chosen.T


@functools.partial(jax.jit, static_argnames=("chunk",))
def boruvka_mst_batch(weights: jax.Array, chunk: int | None = None
                      ) -> jax.Array:
    """Batched :func:`boruvka_mst`: (b, d, d) weights -> (b, d, d) bools.

    ``chunk=None`` is the plain ``vmap`` (one fused launch for the whole
    trial stack). With ``chunk`` set, the batch streams through
    ``lax.map`` in ``chunk``-sized vmapped slabs, so the solver's
    transient working set (the per-trial key/component scratch) scales
    with ``chunk`` instead of b — the memory-budgeted metrics stage of
    ``experiments.run_trials`` at large d. Trials are independent, so the
    chunked result is bit-identical per trial to the full vmap; the batch
    zero-pads to a chunk multiple (an all-zero weight matrix still runs —
    only the order of weights matters — and is sliced off).
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
    """Explicit host step: a structure -> canonical edge list, the (j, k),
    j < k, edges in row-major order of the upper triangle.

    ``adj`` is either a symmetric (d, d) bool adjacency (a glasso support,
    ``StreamingGram``) or the int32 edge record of
    ``boruvka_mst(w, edges=True)``, whose O(d log d) entries are read back
    and deduplicated instead of a (d, d) scan; both forms of one tree give
    the same list.

    The read-back (which waits for the device) is one
    ``repro.structure.fetch`` span, the host extraction one
    ``repro.structure.edges`` span."""
    with span("structure.fetch"):
        adj = np.asarray(adj)
    with span("structure.edges"):
        if adj.dtype == bool:
            iu, ju = np.nonzero(np.triu(adj, k=1))
        else:
            d = adj.shape[-1]
            ties = adj[adj != np.iinfo(np.int32).max]
            j, k = np.divmod(ties, d)
            iu, ju = np.divmod(np.unique(np.minimum(j, k) * d
                                         + np.maximum(j, k)), d)
        return list(zip(iu.tolist(), ju.tolist()))


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
        return adjacency_to_edges(boruvka_mst(jnp.asarray(weights),
                                              edges=True))
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
    shared edge order.
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
    device solver directly, and only the solve's edge record
    (``boruvka_mst(w, edges=True)``: O(d log d) int32, not the (d, d)
    adjacency) crosses to the host.

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
            record = boruvka_mst(w, edges=True)
        return adjacency_to_edges(record)
    with span("structure.mst"):
        return kruskal_mst(np.asarray(w))
