"""Sampling from tree-structured GGMs.

Three samplers are provided:
  * ``sample_ggm`` — generic: Cholesky of the full correlation matrix.
  * ``sample_tree_ggm`` — topological: exploits the tree factorization
    p(x) = p(x_root) prod p(x_child | x_parent); for an edge (p, c) with
    correlation rho the conditional is N(rho * x_p, 1 - rho^2). This is the
    sampler the paper's synthetic experiments imply (random weighted tree
    -> eq. 24 covariance -> i.i.d. normals).
  * ``sample_tree_ggm_parents`` — the same law in topological parent-array
    form (see ``trees.topological_parents``): a single matmul against the
    path-product mixer, pure and jit-able with no host preprocessing, and
    ``sample_tree_ggm_batch`` vmaps it over stacked (key, parent, rho)
    trial axes.
  * ``sample_tree_ggm_rows`` — the same law again with per-row PRNG keys,
    making the draws independent of the total row count: the first m rows
    of an (n, d) draw equal the (m, d) draw bit-for-bit. This is the
    sampling stage of the bucketed sweep engine
    (``experiments.run_trials``), where n is padded up to a shape bucket
    and masked; ``sample_tree_ggm_rows_batch`` is its vmapped trial form.
  * ``sample_ggm_rows`` / ``sample_ggm_rows_batch`` — the same row-keyed,
    bucket-stable contract for ARBITRARY covariances via a Cholesky
    factor: the data plane of the sparse trial plane
    (``glasso.random_sparse_precision`` ground truths).

All samplers are exact: x = M @ (c * z) with M the unit lower-triangular
path-product matrix solves the conditional recursion in closed form, so
cov(x) is exactly the eq.-24 correlation matrix.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import trees

#: f32 matmuls run at full precision: the TPU's default rounds operands
#: to bf16, which would put the samples and solves off their f32 reference
_HI = jax.lax.Precision.HIGHEST


def bfs_order(d: int, edges: list[tuple[int, int]], root: int = 0):
    """Return (order, parent, parent_weight_index): a BFS node ordering with
    each node's parent and the index of the connecting edge."""
    nbrs: list[list[tuple[int, int]]] = [[] for _ in range(d)]
    for idx, (j, k) in enumerate(edges):
        nbrs[j].append((k, idx))
        nbrs[k].append((j, idx))
    order = [root]
    parent = [-1] * d
    pedge = [-1] * d
    seen = [False] * d
    seen[root] = True
    head = 0
    while head < len(order):
        node = order[head]
        head += 1
        for child, eidx in nbrs[node]:
            if not seen[child]:
                seen[child] = True
                parent[child] = node
                pedge[child] = eidx
                order.append(child)
    return np.array(order), np.array(parent), np.array(pedge)


def sample_tree_ggm_parents(
    key: jax.Array,
    n: int,
    parent: jax.Array,
    rho: jax.Array,
) -> jax.Array:
    """Draw ``n`` samples from the tree GGM in parent-array form.

    ``parent``/``rho``: (d,) topological arrays (``parent[t] < t``,
    ``rho[0] = 0``). Pure jnp with static shapes — jit-able and the unit
    the trial plane vmaps over. Returns (n, d) float32, unit variances.
    """
    d = parent.shape[0]
    rho = jnp.asarray(rho, jnp.float32)
    c = jnp.sqrt(jnp.clip(1.0 - jnp.square(rho), 0.0, None)).at[0].set(1.0)
    z = jax.random.normal(key, (n, d), dtype=jnp.float32)
    M = trees.path_product_mixer(parent, rho)
    return jnp.matmul(z * c[None, :], M.T, precision=_HI)


def sample_tree_ggm_batch(
    keys: jax.Array,
    n: int,
    parents: jax.Array,
    rhos: jax.Array,
) -> jax.Array:
    """Batched trial sampler: one tree GGM per leading index.

    ``keys``: (t,) PRNG keys; ``parents``/``rhos``: (t, d) stacked
    topological arrays. Returns (t, n, d) float32 — the data plane of
    ``experiments.run_trials``, one vmapped call for all trials.
    """
    return jax.vmap(sample_tree_ggm_parents, in_axes=(0, None, 0, 0))(
        keys, n, parents, rhos)


def sample_tree_ggm_rows(
    key: jax.Array,
    n: int,
    parent: jax.Array,
    rho: jax.Array,
) -> jax.Array:
    """Shape-stable tree-GGM sampler: row i depends only on (key, i).

    Same law as :func:`sample_tree_ggm_parents`, but the driving normals
    are drawn per-row from ``fold_in(key, i)`` instead of one (n, d) call,
    so the first ``m`` rows of an (n, d) draw are BIT-EQUAL to the full
    (m, d) draw for every m <= n. This is the sampling stage of the
    bucketed trial plane (``experiments.run_trials``): padding n up to a
    bucket and masking rows >= n_valid yields exactly the draws of the
    unpadded sweep, point for point — and sharding the trial axis over a
    mesh cannot change them either (each trial folds its own key).
    """
    return sample_tree_ggm_rows_batch(
        key[None], n, parent[None], rho[None])[0]


def _row_normals(keys: jax.Array, n: int, d: int) -> jax.Array:
    """(t,) trial keys -> (t, n, d) standard normals with row i of trial k
    drawn from ``fold_in(keys[k], i)`` — the shape-stable driving noise of
    every bucketed sampler (the first m rows of an (n, d) draw are
    bit-equal to the (m, d) draw).

    The (t, n) per-row keys are folded in one flat vmap (not a nested
    per-trial vmap of ``normal(k, (d,))`` — that shape compiles ~3x
    slower).
    """
    t = keys.shape[0]
    row_keys = jax.vmap(
        lambda k: jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            k, jnp.arange(n, dtype=jnp.uint32)))(keys)
    return jax.vmap(lambda k: jax.random.normal(k, (d,), jnp.float32))(
        row_keys.reshape(t * n)).reshape(t, n, d)


def sample_tree_ggm_rows_batch(
    keys: jax.Array,
    n: int,
    parents: jax.Array,
    rhos: jax.Array,
) -> jax.Array:
    """Batched :func:`sample_tree_ggm_rows`: (t,) keys + (t, d) stacked
    topological arrays -> (t, n, d) float32. The data plane of the bucketed
    sweep engine — one call for all trials, rows stable in n; the
    per-trial conditional mixing is one batched einsum.
    """
    d = parents.shape[-1]
    rhos = jnp.asarray(rhos, jnp.float32)
    z = _row_normals(keys, n, d)
    c = jnp.sqrt(jnp.clip(1.0 - jnp.square(rhos), 0.0, None)).at[:, 0].set(1.0)
    M = jax.vmap(trees.path_product_mixer)(parents, rhos)
    return jnp.einsum("tnd,ted->tne", z * c[:, None, :], M, precision=_HI)


def sample_ggm_rows(key: jax.Array, n: int, chol: jax.Array) -> jax.Array:
    """Shape-stable generic GGM sampler: row i depends only on (key, i).

    ``chol``: (d, d) lower-triangular Cholesky factor of the target
    covariance (x = L z). Same bucket-stability contract as
    :func:`sample_tree_ggm_rows` — the sampling stage of the SPARSE trial
    plane, where the covariance comes from
    ``glasso.random_sparse_precision`` instead of a tree.
    """
    return sample_ggm_rows_batch(key[None], n, chol[None])[0]


def sample_ggm_rows_batch(
    keys: jax.Array, n: int, chols: jax.Array
) -> jax.Array:
    """Batched :func:`sample_ggm_rows`: (t,) keys + (t, d, d) stacked
    Cholesky factors -> (t, n, d) float32. The data plane of the sparse
    sweep engine (``experiments.run_trials`` on a sparse plan): one call
    for all trials, rows bit-stable in n, so bucket padding and trial-axis
    sharding cannot change any trial's draws.
    """
    d = chols.shape[-1]
    z = _row_normals(keys, n, d)
    return jnp.einsum("tnd,ted->tne", z, jnp.asarray(chols, jnp.float32),
                      precision=_HI)


def sample_tree_ggm(
    key: jax.Array,
    n: int,
    d: int,
    edges: list[tuple[int, int]],
    weights: np.ndarray,
) -> jax.Array:
    """Draw ``n`` i.i.d. samples from the tree GGM with unit variances.

    Host-facing wrapper over :func:`sample_tree_ggm_parents`: converts the
    edge list to topological form, samples on device, and returns columns
    in the ORIGINAL node labelling. Returns an (n, d) float32 array.
    """
    parent, rho, perm = trees.topological_parents(d, edges, weights)
    x_topo = sample_tree_ggm_parents(key, n, jnp.asarray(parent),
                                     jnp.asarray(rho))
    inv = np.empty(d, dtype=np.int64)
    inv[perm] = np.arange(d)
    return x_topo[:, jnp.asarray(inv)]


def sample_ggm(key: jax.Array, n: int, corr: np.ndarray) -> jax.Array:
    """Generic GGM sampler via Cholesky of the correlation matrix."""
    d = corr.shape[0]
    chol = np.linalg.cholesky(np.asarray(corr, dtype=np.float64) + 1e-12 * np.eye(d))
    z = jax.random.normal(key, (n, d), dtype=jnp.float32)
    return jnp.matmul(z, jnp.asarray(chol.T, dtype=jnp.float32),
                      precision=_HI)
