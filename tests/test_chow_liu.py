"""MWST solvers (Kruskal host / Boruvka device) + Chow-Liu pipelines."""
import functools
import re

import numpy as np
import jax.numpy as jnp
import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import chow_liu as CL
from repro.core import sampler, trees


def _random_weights(d, seed):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(d, d))
    w = (w + w.T) / 2
    np.fill_diagonal(w, 0.0)
    return w


@given(st.integers(2, 24), st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_kruskal_boruvka_agree(d, seed):
    w = _random_weights(d, seed)
    ek = trees.edges_canonical(CL.kruskal_mst(w))
    eb = trees.edges_canonical(
        CL.adjacency_to_edges(np.asarray(CL.boruvka_mst(jnp.asarray(w))))
    )
    assert ek == eb


def test_boruvka_handles_ties():
    """Identical weights everywhere — any spanning tree is optimal; the
    result must still be a tree and match Kruskal's tie-breaking."""
    d = 8
    w = np.ones((d, d)) - np.eye(d)
    ek = CL.kruskal_mst(w)
    eb = CL.adjacency_to_edges(np.asarray(CL.boruvka_mst(jnp.asarray(w))))
    assert trees.is_tree(d, ek) and trees.is_tree(d, eb)
    assert trees.edges_canonical(ek) == trees.edges_canonical(eb)


def _rank_boruvka(weights):
    """Reference: the rank-and-scatter Boruvka the dense solver replaced
    (a stable argsort ranks the flat weights, scatters build the ranks and
    merge the components). Its tree is the one the dense solver must give,
    bit for bit."""
    d = weights.shape[0]
    flat = weights.reshape(-1)
    order = jnp.argsort(-flat, stable=True)
    ranks = jnp.zeros(d * d, jnp.int32).at[order].set(
        jnp.arange(d * d, 0, -1, dtype=jnp.int32))
    r = ranks.reshape(d, d)
    r = jnp.maximum(r, r.T)
    W = jnp.where(jnp.eye(d, dtype=bool), -1, r)
    n_jump = int(np.ceil(np.log2(max(d, 2)))) + 1

    def round_body(state):
        comp, sel, _ = state
        cross = comp[:, None] != comp[None, :]
        Wm = jnp.where(cross, W, -1)
        best_w = Wm.max(axis=1)
        best_k = Wm.argmax(axis=1).astype(jnp.int32)
        seg_best = jax.ops.segment_max(best_w, comp, num_segments=d)
        has_edge = seg_best >= 0
        is_best = (best_w == seg_best[comp]) & (best_w >= 0)
        node_score = jnp.where(is_best, d - jnp.arange(d, dtype=jnp.int32), 0)
        seg_node = jax.ops.segment_max(node_score, comp, num_segments=d)
        valid = has_edge & (seg_node > 0)
        j_sel = jnp.where(valid, d - seg_node, 0).astype(jnp.int32)
        k_sel = jnp.where(valid, best_k[j_sel], 0).astype(jnp.int32)
        sel = sel.at[j_sel, k_sel].max(valid)
        sel = sel.at[k_sel, j_sel].max(valid)
        cj, ck = comp[j_sel], comp[k_sel]
        node = jnp.arange(d, dtype=jnp.int32)
        hi = jnp.where(valid, jnp.maximum(cj, ck), node)
        lo = jnp.where(valid, jnp.minimum(cj, ck), node)
        parent = node.at[hi].min(lo)
        parent = jax.lax.fori_loop(0, n_jump, lambda _, p: p[p], parent)
        comp = parent[comp]
        return comp, sel, jnp.sum(jnp.bincount(comp, length=d) > 0)

    init = (jnp.arange(d, dtype=jnp.int32), jnp.zeros((d, d), dtype=bool),
            jnp.asarray(d, dtype=jnp.int32))
    return jax.lax.while_loop(lambda s: s[2] > 1, round_body, init)[1]


_rank_boruvka_jit = jax.jit(_rank_boruvka)

_FAMILIES = ("normal", "equal", "int_dup", "asymmetric", "signed_zero",
             "nonfinite", "rounded")


def _family_weights(family, shape, seed):
    """float32 weights of one family; the last two axes are (d, d)."""
    rng = np.random.default_rng(seed)
    if family == "normal":
        w = rng.normal(size=shape)
        w = (w + np.swapaxes(w, -1, -2)) / 2
    elif family == "equal":
        w = np.ones(shape)
    elif family == "int_dup":
        w = rng.integers(0, 4, size=shape).astype(float)
        w = np.maximum(w, np.swapaxes(w, -1, -2))
    elif family == "asymmetric":
        w = rng.normal(size=shape)
    elif family == "signed_zero":  # the tree rests on ties of -0.0 and +0.0
        w = rng.choice([0.0, -0.0, -1.0], size=shape)
    elif family == "nonfinite":
        w = rng.choice([np.nan, np.inf, -np.inf, 0.5, -0.5, 0.0], size=shape)
        w[rng.random(shape) < 0.3] = -np.nan
    else:  # rounded: many exact ties among distinct values
        w = np.round(rng.normal(size=shape), 1)
        w = (w + np.swapaxes(w, -1, -2)) / 2
    return jnp.asarray(w.astype(np.float32))


@pytest.mark.parametrize("family", _FAMILIES)
@pytest.mark.parametrize("d", [2, 3, 5, 8, 20, 33, 130, 257])
def test_boruvka_bit_identical_to_rank_reference(d, family):
    w = _family_weights(family, (d, d), seed=1000 * d + len(family))
    got = np.asarray(CL.boruvka_mst(w))
    np.testing.assert_array_equal(got, np.asarray(_rank_boruvka_jit(w)))


@pytest.mark.parametrize("solve", ["vmap", "batch", "batch_chunk7"])
def test_boruvka_batches_bit_identical_to_rank_reference(solve):
    """280 trials at d=20, 40 of each family: the vmapped solver, the batch
    and 7-trial slabs."""
    W = jnp.concatenate([_family_weights(f, (40, 20, 20), seed=i)
                         for i, f in enumerate(_FAMILIES)])
    ref = np.asarray(jax.jit(jax.vmap(_rank_boruvka))(W))
    if solve == "vmap":
        got = jax.jit(jax.vmap(CL.boruvka_mst))(W)
    elif solve == "batch":
        got = CL.boruvka_mst_batch(W)
    else:
        got = CL.boruvka_mst_batch(W, chunk=7)
    np.testing.assert_array_equal(np.asarray(got), ref)


def _kruskal_on_edge_order(w):
    """Reference for any (d, d) weights, asymmetric and non-finite ones
    included: ``kruskal_mst`` on distinct ranks of the order the device
    solve uses (edge {j,k} takes the larger orientation, NaN below -inf,
    ties to the smaller flat index), as (j, k), j < k, edges in row-major
    order. On symmetric finite weights it is ``kruskal_mst(w)`` sorted."""
    w = np.asarray(w, np.float64)
    d = len(w)
    real = ~np.isnan(w)
    v = np.where(real, w, -np.inf)
    flat = np.arange(d * d).reshape(d, d)
    own = (real & ~real.T) | ((real == real.T) & (
        (v > v.T) | ((v == v.T) & (flat < flat.T))))
    real, v, tie = (np.where(own, a, a.T) for a in (real, v, flat))
    iu, ju = np.triu_indices(d, 1)
    order = np.lexsort((tie[iu, ju], -v[iu, ju], ~real[iu, ju]))
    ranks = np.zeros((d, d))
    ranks[iu[order], ju[order]] = np.arange(len(order), 0, -1)
    return sorted(CL.kruskal_mst(ranks + ranks.T))


@pytest.mark.parametrize("family", _FAMILIES)
@pytest.mark.parametrize("d", [2, 3, 5, 8, 20, 33, 130, 257, 1024])
def test_boruvka_edge_record_gives_the_adjacency_edges(d, family):
    """The compact result of the same solve: the same edge list, in the
    same order, as the adjacency's, and Kruskal's tree, from a record of
    at most ceil(log2 d) rounds."""
    w = _family_weights(family, (d, d), seed=7 * d + len(family))
    record = CL.boruvka_mst(w, edges=True)
    got = CL.adjacency_to_edges(record)
    assert got == CL.adjacency_to_edges(CL.boruvka_mst(w))
    assert got == _kruskal_on_edge_order(w)
    assert len(got) == d - 1
    assert record.dtype == jnp.int32
    assert record.shape == (int(np.ceil(np.log2(d))), d)


@pytest.mark.parametrize("solve", ["single", "vmap", "batch",
                                   "batch_chunk7"])
def test_boruvka_default_returns_bool_adjacency(solve):
    """Callers that read the adjacency (the sweep's metrics, serving,
    ``StreamingGram``) get the (d, d) bool by default."""
    W = _family_weights("normal", (9, 12, 12), seed=3)
    if solve == "single":
        got, want = CL.boruvka_mst(W[0]), (12, 12)
    elif solve == "vmap":
        got, want = jax.vmap(CL.boruvka_mst)(W), (9, 12, 12)
    elif solve == "batch":
        got, want = CL.boruvka_mst_batch(W), (9, 12, 12)
    else:
        got, want = CL.boruvka_mst_batch(W, chunk=7), (9, 12, 12)
    assert got.dtype == jnp.bool_ and got.shape == want
    np.testing.assert_array_equal(np.asarray(got), np.swapaxes(got, -1, -2))


@pytest.mark.parametrize("fn,shape", [
    (CL.boruvka_mst, (1024, 1024)),
    (CL.boruvka_mst_batch, (6000, 20, 20)),
    (functools.partial(CL.boruvka_mst_batch, chunk=1000), (6000, 20, 20)),
    (functools.partial(CL.boruvka_mst, edges=True), (1024, 1024)),
], ids=["single_d1024", "batch", "batch_chunk1000", "single_d1024_edges"])
def test_boruvka_lowers_without_scatter_gather_sort(fn, shape):
    """Data-dependent indexed updates and reads run element by element on
    a TPU; the solver is built of dense compares and reductions only."""
    text = jax.jit(fn).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32)).as_text()
    assert "stablehlo.while" in text
    for op in ("scatter", "gather", "sort"):
        assert not re.search(rf"stablehlo\.{op}\b", text), op


def test_mwst_maximizes_weight():
    """Against brute force on small graphs."""
    import itertools

    d = 6
    for seed in range(5):
        w = _random_weights(d, seed)
        best, best_w = None, -np.inf
        nodes = range(d)
        # brute force over all labelled trees via Pruefer sequences
        for pruefer in itertools.product(nodes, repeat=d - 2):
            rng_edges = _pruefer_to_tree(list(pruefer), d)
            tw = sum(w[j, k] for j, k in rng_edges)
            if tw > best_w:
                best, best_w = rng_edges, tw
        got = CL.kruskal_mst(w)
        got_w = sum(w[j, k] for j, k in got)
        assert got_w == pytest.approx(best_w)


def _pruefer_to_tree(prufer, d):
    degree = np.ones(d, dtype=int)
    for v in prufer:
        degree[v] += 1
    edges = []
    for v in prufer:
        leaf = int(np.flatnonzero(degree == 1)[0])
        edges.append((leaf, v))
        degree[leaf] = 0
        degree[v] -= 1
    rest = np.flatnonzero(degree == 1)
    edges.append((int(rest[0]), int(rest[1])))
    return edges


def test_exact_weights_recover_exactly():
    """With the TRUE MI as weights, Chow-Liu returns the true tree."""
    rng = np.random.default_rng(7)
    d = 25
    edges = trees.random_tree(d, rng)
    w_edges = rng.uniform(0.3, 0.9, size=d - 1)
    Q = trees.tree_correlation_matrix(d, edges, w_edges)
    mi = -0.5 * np.log1p(-np.clip(Q**2, 0, 1 - 1e-12))
    np.fill_diagonal(mi, 0.0)
    est = CL.kruskal_mst(mi)
    assert trees.tree_edit_distance(edges, est) == 0


@pytest.mark.parametrize("method,rate", [("sign", 1), ("persymbol", 1),
                                         ("persymbol", 4), ("original", 0)])
def test_end_to_end_recovery(method, rate):
    """learn_structure recovers a 15-node tree from 8k samples for every
    method (the paper's core claim at generous n)."""
    rng = np.random.default_rng(11)
    d, n = 15, 8_000
    edges = trees.random_tree(d, rng)
    w = rng.uniform(0.5, 0.85, size=d - 1)
    x = sampler.sample_tree_ggm(jax.random.key(4), n, d, edges, w)
    est = CL.learn_structure(x, method=method, rate=max(rate, 1))
    assert trees.tree_edit_distance(edges, est) == 0


def test_learn_structure_backends_agree():
    rng = np.random.default_rng(13)
    d, n = 12, 3_000
    edges = trees.random_tree(d, rng)
    w = rng.uniform(0.4, 0.9, size=d - 1)
    x = sampler.sample_tree_ggm(jax.random.key(5), n, d, edges, w)
    e1 = CL.learn_structure(x, method="sign", backend="kruskal")
    e2 = CL.learn_structure(x, method="sign", backend="boruvka")
    assert trees.edges_canonical(e1) == trees.edges_canonical(e2)


def test_unknown_method_raises():
    with pytest.raises(ValueError):
        CL.learn_structure(jnp.zeros((10, 3)), method="nope")
    with pytest.raises(ValueError):
        CL.chow_liu(np.zeros((3, 3)), backend="nope")
