"""Tree utilities for tree-structured Gaussian graphical models.

Implements the synthetic-data machinery of the paper: random trees, the
correlation-decay covariance construction (eq. 24: rho_rs = prod of edge
correlations on Path(r,s)), structure comparison, and the human-skeleton
topology used in the Figs. 10-11 experiment.

Two representations coexist:

* **edge lists** (host): ``[(j, k), ...]`` — the human-facing form used by
  the reference pipelines and the paper's notation.
* **topological parent arrays** (device): nodes relabelled in BFS order so
  node ``t > 0`` has ``parent[t] < t`` with edge correlation ``rho[t]``
  (``parent[0] = 0``, ``rho[0] = 0``). This form is pure data — jit-able,
  vmap-able over stacked trees — and feeds the batched sampler, the
  eq.-24 covariance (:func:`tree_correlation`) and the device-side
  structure metrics (:func:`structure_error`, :func:`structure_hamming`,
  :func:`edge_f1`) used by the on-device trial plane.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

#: f32 matmuls run at full precision: the TPU's default rounds operands
#: to bf16
_HI = jax.lax.Precision.HIGHEST


def random_tree(d: int, rng: np.random.Generator) -> list[tuple[int, int]]:
    """Uniform random labelled tree on ``d`` nodes via a Pruefer sequence."""
    if d < 2:
        return []
    if d == 2:
        return [(0, 1)]
    prufer = rng.integers(0, d, size=d - 2)
    degree = np.ones(d, dtype=np.int64)
    for v in prufer:
        degree[v] += 1
    edges = []
    # min-leaf scan per step (d is small in all experiments; O(d^2) is fine)
    for v in prufer:
        leaf = int(np.flatnonzero(degree == 1)[0])
        edges.append((leaf, int(v)))
        degree[leaf] = 0
        degree[v] -= 1
    remaining = np.flatnonzero(degree == 1)
    edges.append((int(remaining[0]), int(remaining[1])))
    return edges


def chain_tree(d: int) -> list[tuple[int, int]]:
    return [(i, i + 1) for i in range(d - 1)]


def star_tree(d: int, center: int = 0) -> list[tuple[int, int]]:
    return [(center, j) for j in range(d) if j != center]


# 20-joint Kinect-style human skeleton (MAD dataset layout), used for the
# Figs. 10-11 reproduction. Node 0 is the hip-center root.
SKELETON_JOINTS = [
    "hip_center", "spine", "shoulder_center", "head",
    "shoulder_l", "elbow_l", "wrist_l", "hand_l",
    "shoulder_r", "elbow_r", "wrist_r", "hand_r",
    "hip_l", "knee_l", "ankle_l", "foot_l",
    "hip_r", "knee_r", "ankle_r", "foot_r",
]

SKELETON_EDGES = [
    (0, 1), (1, 2), (2, 3),
    (2, 4), (4, 5), (5, 6), (6, 7),
    (2, 8), (8, 9), (9, 10), (10, 11),
    (0, 12), (12, 13), (13, 14), (14, 15),
    (0, 16), (16, 17), (17, 18), (18, 19),
]


def tree_adjacency(d: int, edges: list[tuple[int, int]]) -> np.ndarray:
    adj = np.zeros((d, d), dtype=bool)
    for j, k in edges:
        adj[j, k] = adj[k, j] = True
    return adj


def tree_correlation_matrix(
    d: int, edges: list[tuple[int, int]], weights: np.ndarray
) -> np.ndarray:
    """Full correlation matrix from edge correlations via eq. (24):
    rho_rs = prod_{e in Path(r,s)} rho_e.

    Computed by BFS from each root accumulating products along paths.
    Result is a valid correlation matrix of a tree-structured GGM with unit
    variances (the paper's standing normalization Q_jj = 1).
    """
    weights = np.asarray(weights, dtype=np.float64)
    assert len(edges) == d - 1 and weights.shape == (d - 1,)
    nbrs: list[list[tuple[int, float]]] = [[] for _ in range(d)]
    for (j, k), w in zip(edges, weights):
        nbrs[j].append((k, float(w)))
        nbrs[k].append((j, float(w)))
    Q = np.eye(d)
    for root in range(d):
        # BFS accumulating correlation products
        stack = [(root, -1, 1.0)]
        while stack:
            node, parent, acc = stack.pop()
            for child, w in nbrs[node]:
                if child == parent:
                    continue
                Q[root, child] = acc * w
                stack.append((child, node, acc * w))
    return Q


# --------------------------------------------------------------------------
# Topological parent-array form + device-side (jnp) tree machinery
# --------------------------------------------------------------------------

def topological_parents(
    d: int,
    edges: list[tuple[int, int]],
    weights,
    root: int = 0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Relabel a weighted tree into topological parent-array form.

    Returns ``(parent, rho, perm)``: int32/float32 arrays of shape (d,)
    with ``parent[t] < t`` for ``t > 0`` (``parent[0] = 0``, ``rho[0] =
    0``), and ``perm`` mapping new labels to the original ones
    (``perm[t] = original node at topological position t``). Relabelling
    is a global permutation, so structure metrics computed in either
    labelling agree.
    """
    weights = np.asarray(weights, dtype=np.float32)
    assert len(edges) == d - 1 and weights.shape == (d - 1,)
    nbrs: list[list[tuple[int, float]]] = [[] for _ in range(d)]
    for (j, k), w in zip(edges, weights):
        nbrs[j].append((k, float(w)))
        nbrs[k].append((j, float(w)))
    perm = np.empty(d, dtype=np.int64)
    parent = np.zeros(d, dtype=np.int32)
    rho = np.zeros(d, dtype=np.float32)
    pos = np.empty(d, dtype=np.int64)  # original label -> topological slot
    perm[0] = root
    pos[root] = 0
    seen = [False] * d
    seen[root] = True
    head, tail = 0, 1
    while head < tail:
        node = int(perm[head])
        head += 1
        for child, w in nbrs[node]:
            if not seen[child]:
                seen[child] = True
                perm[tail] = child
                pos[child] = tail
                parent[tail] = pos[node]
                rho[tail] = w
                tail += 1
    assert tail == d, "edges do not span a connected tree"
    return parent, rho, perm


def adjacency_from_parents(parent: jax.Array) -> jax.Array:
    """(d,) topological parent array -> symmetric (d, d) bool adjacency.

    Pure jnp: jit- and vmap-able (stack parents over a leading trial axis).
    """
    parent = jnp.asarray(parent)
    d = parent.shape[-1]
    idx = jnp.arange(d)
    half = (idx[:, None] == parent[..., None, :]) & (idx[None, :] > 0)
    # half[..., p, t] = (parent[t] == p) for t > 0: edge (t, parent[t])
    return half | jnp.swapaxes(half, -1, -2)


def path_product_mixer(parent: jax.Array, rho: jax.Array) -> jax.Array:
    """Lower-triangular path-product matrix M with x = M @ (c * z).

    Solves x_t = rho_t x_{parent(t)} + c_t z_t, i.e. M = (I - B)^{-1} with
    B[t, parent[t]] = rho_t strictly lower triangular (topological
    labelling). B is nilpotent, so the inverse is the finite product
    ``prod_k (I + B^(2^k))`` — ceil(log2 d) matmuls, no solve, no scan:
    jit- and vmap-able with fixed shapes.
    """
    parent = jnp.asarray(parent)
    rho = jnp.asarray(rho, jnp.float32)
    d = parent.shape[0]
    t = jnp.arange(d)
    B = jnp.zeros((d, d), jnp.float32).at[t, parent].set(
        jnp.where(t > 0, rho, 0.0))
    M = jnp.eye(d, dtype=jnp.float32) + B
    P = B
    for _ in range(max(int(np.ceil(np.log2(max(d, 2)))), 1)):
        P = jnp.matmul(P, P, precision=_HI)
        M = M + jnp.matmul(M, P, precision=_HI)
    return M


def tree_correlation(parent: jax.Array, rho: jax.Array) -> jax.Array:
    """Eq. (24) correlation matrix from parent-array form, on device.

    Equals :func:`tree_correlation_matrix` up to the topological
    relabelling: ``Q_dev[t, s] == Q_host[perm[t], perm[s]]``.
    """
    rho = jnp.asarray(rho, jnp.float32)
    c = jnp.sqrt(jnp.clip(1.0 - jnp.square(rho), 0.0, None)).at[0].set(1.0)
    A = path_product_mixer(parent, rho) * c[None, :]
    return jnp.matmul(A, A.T, precision=_HI)


def structure_hamming(adj_a: jax.Array, adj_b: jax.Array) -> jax.Array:
    """Device edge-set symmetric difference |E_a ^ E_b| of two symmetric
    adjacencies — equals host :func:`tree_edit_distance` on the edge
    lists. int32 scalar (batched over leading axes)."""
    diff = jnp.asarray(adj_a) != jnp.asarray(adj_b)
    return jnp.sum(diff, axis=(-2, -1), dtype=jnp.int32) // 2


def structure_error(adj_est: jax.Array, adj_true: jax.Array) -> jax.Array:
    """Device indicator of the paper's error event {T_hat != T}: True iff
    the two adjacencies differ anywhere. Bool scalar (batched over
    leading axes)."""
    return jnp.any(jnp.asarray(adj_est) != jnp.asarray(adj_true),
                   axis=(-2, -1))


def edge_counts(
    adj_est: jax.Array, adj_true: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Integer edge-count channels of a support comparison: ``(shared,
    est_edges, true_edges)`` = (|E_hat & E|, |E_hat|, |E|) as int32 scalars
    (batched over leading axes).

    These are the exact channels precision / recall / F1 are recovered
    from AFTER any reduction: P = shared/est, R = shared/true,
    F1 = 2*shared/(est + true). Because each channel is integer-valued,
    their sums are exact in f32 under any reduction order — the property
    the trial plane's 1-vs-N-device parity gates rest on. For spanning
    trees est = true = d-1, so F1 degenerates to the shared/(d-1)
    identity the tree plane uses; general sparse supports need all three
    channels.
    """
    est, true = jnp.broadcast_arrays(
        jnp.asarray(adj_est), jnp.asarray(adj_true))
    shared = jnp.sum(est & true, axis=(-2, -1), dtype=jnp.int32) // 2
    n_est = jnp.sum(est, axis=(-2, -1), dtype=jnp.int32) // 2
    n_true = jnp.sum(true, axis=(-2, -1), dtype=jnp.int32) // 2
    return shared, n_est, n_true


def edge_f1(adj_est: jax.Array, adj_true: jax.Array) -> jax.Array:
    """Device edge-level F1 = 2 TP / (2 TP + FP + FN); 1.0 iff identical
    (both inputs symmetric bool). Float32 scalar (batched)."""
    est = jnp.asarray(adj_est)
    true = jnp.asarray(adj_true)
    tp = jnp.sum(est & true, axis=(-2, -1)).astype(jnp.float32)
    fp = jnp.sum(est & ~true, axis=(-2, -1)).astype(jnp.float32)
    fn = jnp.sum(~est & true, axis=(-2, -1)).astype(jnp.float32)
    return 2.0 * tp / jnp.maximum(2.0 * tp + fp + fn, 1.0)


def edges_canonical(edges) -> set[tuple[int, int]]:
    return {(min(j, k), max(j, k)) for j, k in edges}


def tree_edit_distance(e1, e2) -> int:
    """Number of edges present in exactly one of the two trees (symmetric
    difference size). Zero iff identical structure."""
    s1, s2 = edges_canonical(e1), edges_canonical(e2)
    return len(s1 ^ s2)


def is_tree(d: int, edges) -> bool:
    if len(edges) != d - 1:
        return False
    parent = list(range(d))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for j, k in edges:
        rj, rk = find(j), find(k)
        if rj == rk:
            return False
        parent[rj] = rk
    return True
