"""Plain reference of the tree-GGM pipeline (arXiv 1809.08067 §3-§5).

Imports nothing of the program. Ground-truth trees follow the sweep's
documented draw (trial ``rep`` uses ``np.random.default_rng(seed0 + rep)``:
a Pruefer-sequence tree, then edge correlations Uniform[rho_min, rho_max]);
the driving normals follow its documented keying (``fold_in(key(seed0),
rep)``, then ``fold_in(., row)``, one ``normal((d,))`` per row). From there
on everything is numpy: the tree recursion x_t = rho_t x_parent + c_t z_t
in float64, the wire encodings, the Gram, the Chow-Liu weights (eqs. 1, 4,
8, 30), and a maximum-weight spanning tree by Prim over distinct ranks
(ties go to the smaller row-major index, the documented tie rule).

Integer counts accumulate exactly (int32), or in int16, wrapping, for the
control of the cells whose configuration states int32 counts
(``counts="int16"``). ``lower`` names the steps that a control computes one
precision below what the configurations state (``LOWER``).
"""
from __future__ import annotations

import numpy as np
from scipy.special import ndtri

#: the steps a control may lower, each one step below the precision the
#: configurations state: float32 at Precision.HIGHEST -> Precision.HIGH for
#: the sampling and the code and value Grams, int32 -> int16 counts,
#: float32 -> bfloat16 weights
LOWER = ("sampling", "grams", "counts", "weights")


def bf16(a) -> np.ndarray:
    """``a`` rounded to bfloat16 (nearest even), held in float32."""
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def high(a) -> np.ndarray:
    """``a`` as a Precision.HIGH matmul holds a float32 operand: a bfloat16
    pair hi + lo, 16 significant bits (the lo * lo product it also drops
    lies below that rounding)."""
    a = np.asarray(a, np.float32)
    hi = bf16(a)
    return hi + bf16(a - hi)


# --------------------------------------------------------------------------
# ground truth and data
# --------------------------------------------------------------------------

def random_tree(d: int, rng: np.random.Generator) -> list[tuple[int, int]]:
    """Uniform labelled tree from a Pruefer sequence (min-leaf decoding)."""
    if d == 2:
        return [(0, 1)]
    prufer = rng.integers(0, d, size=d - 2)
    degree = np.ones(d, dtype=np.int64)
    np.add.at(degree, prufer, 1)
    edges = []
    for v in prufer:
        leaf = int(np.flatnonzero(degree == 1)[0])
        edges.append((leaf, int(v)))
        degree[leaf] = 0
        degree[v] -= 1
    rest = np.flatnonzero(degree == 1)
    edges.append((int(rest[0]), int(rest[1])))
    return edges


def topological(d: int, edges, weights) -> tuple[np.ndarray, np.ndarray]:
    """BFS relabelling from node 0: (parent, rho) with parent[t] < t."""
    weights = np.asarray(weights, np.float32)
    nbrs: list[list[tuple[int, float]]] = [[] for _ in range(d)]
    for (j, k), w in zip(edges, weights):
        nbrs[j].append((k, w))
        nbrs[k].append((j, w))
    order, pos = [0], {0: 0}
    parent = np.zeros(d, np.int64)
    rho = np.zeros(d, np.float32)
    head = 0
    while head < len(order):
        node = order[head]
        head += 1
        for child, w in nbrs[node]:
            if child not in pos:
                pos[child] = len(order)
                parent[len(order)] = pos[node]
                rho[len(order)] = w
                order.append(child)
    return parent, rho


def draw_trees(d: int, reps: int, rho_min: float, rho_max: float,
               seed0: int) -> tuple[np.ndarray, np.ndarray]:
    """(parents, rhos), each (reps, d), in topological labelling."""
    parents = np.zeros((reps, d), np.int64)
    rhos = np.zeros((reps, d), np.float32)
    for rep in range(reps):
        rng = np.random.default_rng(seed0 + rep)
        edges = random_tree(d, rng)
        w = rng.uniform(rho_min, rho_max, size=d - 1)
        parents[rep], rhos[rep] = topological(d, edges, w)
    return parents, rhos


def true_adjacency(parents: np.ndarray) -> np.ndarray:
    B, d = parents.shape
    adj = np.zeros((B, d, d), bool)
    b = np.repeat(np.arange(B), d - 1)
    t = np.tile(np.arange(1, d), B)
    p = parents[:, 1:].reshape(-1)
    adj[b, t, p] = adj[b, p, t] = True
    return adj


def row_normals(seed0: int, reps, n: int, d: int) -> np.ndarray:
    """(len(reps), d, n) float32 driving normals, feature-major, drawn on
    the default JAX device by the documented keying, one call a block."""
    import jax.numpy as jnp

    return np.asarray(_normals_fn(n, d)(
        jnp.uint32(seed0), jnp.asarray(np.asarray(reps), jnp.uint32)))


_NORMALS = {}


def _normals_fn(n: int, d: int):
    import jax
    import jax.numpy as jnp

    if (n, d) not in _NORMALS:
        def one(key):
            rows = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                key, jnp.arange(n, dtype=jnp.uint32))
            z = jax.vmap(lambda k: jax.random.normal(k, (d,), jnp.float32))(rows)
            return z.T

        def draw(seed0, reps):
            root = jax.random.key(seed0)
            keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(root, reps)
            return jax.vmap(one)(keys)
        _NORMALS[n, d] = jax.jit(draw)
    return _NORMALS[n, d]


def sample(z: np.ndarray, parents: np.ndarray, rhos: np.ndarray,
           lowered: bool = False) -> np.ndarray:
    """(B, d, n) normals -> (B, d, n) tree-GGM samples (feature-major).

    x_t = rho_t x_parent(t) + c_t z_t with c_t = sqrt(1 - rho_t^2),
    x_0 = z_0: the tree's conditional recursion, exact in law, in float64;
    ``lowered``: in float32 over Precision.HIGH operands."""
    B, d, n = z.shape
    rho = rhos.astype(np.float64)
    c = np.sqrt(np.clip(1.0 - rho * rho, 0.0, None))
    c[:, 0] = 1.0
    op, dt = (high, np.float32) if lowered else (np.asarray, np.float64)
    rho, c, z = op(rho), op(c), op(z)
    x = np.empty((B, d, n), dt)
    b = np.arange(B)
    x[:, 0] = z[:, 0]
    for t in range(1, d):
        x[:, t] = (rho[:, t, None] * op(x[b, parents[:, t]])
                   + c[:, t, None] * z[:, t])
    return x


def wrap16(g):
    """Integer counts as an int16 accumulator would hold them."""
    g = np.rint(np.asarray(g, np.float64)).astype(np.int64)
    return ((g + 32768) % 65536 - 32768).astype(np.float64)


# --------------------------------------------------------------------------
# encode -> Gram -> weights -> MWST
# --------------------------------------------------------------------------

def codebook(rate: int) -> tuple[np.ndarray, np.ndarray]:
    """(interior boundaries, centroids) of the R-bit equiprobable quantizer
    of N(0, 1) (§5, eq. 40 with the centroid of a truncated normal)."""
    m = 1 << rate
    a = ndtri(np.arange(1, m) / m)
    edges = np.concatenate([[-np.inf], a, [np.inf]])
    phi = np.where(np.isfinite(edges),
                   np.exp(-np.square(np.where(np.isfinite(edges), edges, 0.0)) / 2)
                   / np.sqrt(2 * np.pi), 0.0)
    return a, m * (phi[:-1] - phi[1:])


def encode(x: np.ndarray, method: str, rate: int,
           codes: dict | None = None) -> tuple[np.ndarray, bool]:
    """(B, d, n) samples -> (operand, integer-valued?). ``codes`` caches
    bin codes across rates: the R-bit bins are unions of the finest bins
    drawn (boundaries Phi^-1(i / 2^R)), so coarser codes are shifts."""
    if method == "sign":
        return np.where(x >= 0, np.float32(1), np.float32(-1)), True
    if method == "original":
        return x, False
    codes = {} if codes is None else codes
    if not codes or max(codes) < rate:
        codes.clear()
        codes[rate] = encode_codes(x, rate)
    top = max(codes)
    c = codes[top] >> (top - rate)
    if rate == 1:  # two antisymmetric levels: c * (+-1), an integer count
        return np.where(c > 0, np.float32(1), np.float32(-1)), True
    return codebook(rate)[1][c], False


def encode_codes(x: np.ndarray, rate: int) -> np.ndarray:
    """R-bit bin codes: the count of interior boundaries below x."""
    return np.searchsorted(codebook(rate)[0], x, side="left").astype(np.int8)


def gram(u: np.ndarray, integer: bool, counts: str = "int32",
         lowered: bool = False) -> np.ndarray:
    """U U^T: integer counts exact (or int16), code and value Grams in
    float64, or ``lowered`` as a Precision.HIGH float32 matmul."""
    if integer:
        g = (u.astype(np.float32) @ u.astype(np.float32).swapaxes(-1, -2)
             ).astype(np.float64)  # exact: |entries| <= n < 2^24
        return wrap16(g) if counts == "int16" else g
    if lowered:
        u = high(u)
        return (u @ u.swapaxes(-1, -2)).astype(np.float64)
    return u @ u.swapaxes(-1, -2)


def weights(g: np.ndarray, n: int, method: str, rate: int) -> np.ndarray:
    """Chow-Liu weights from a Gram of n samples (float64)."""
    if method == "sign":
        theta = np.clip(0.5 + g / (2.0 * n), 1e-7, 1 - 1e-7)
        h = -(theta * np.log2(theta) + (1 - theta) * np.log2(1 - theta))
        return 1.0 - h
    if method == "persymbol" and rate == 1:
        g = g * codebook(1)[1][1] ** 2
    rho = g / n
    if method == "persymbol":
        r2 = (n / (n + 1.0)) * (rho * rho - 1.0 / n)
    else:
        r2 = rho * rho
    return -0.5 * np.log1p(-np.clip(r2, 0.0, 1.0 - 1e-7))


def mwst(w: np.ndarray) -> np.ndarray:
    """(B, d, d) weights -> (B, d, d) bool maximum-weight spanning trees.

    Edges are ranked by descending weight, ties to the smaller row-major
    (j, k), j < k; Prim's algorithm over those distinct ranks."""
    B, d, _ = w.shape
    iu, ju = np.triu_indices(d, 1)
    order = np.argsort(-w[:, iu, ju], axis=1, kind="stable")
    E = iu.size
    rank = np.empty((B, E), np.int64)
    np.put_along_axis(rank, order, np.arange(E, 0, -1)[None, :], axis=1)
    R = np.full((B, d, d), -1, np.int64)
    R[:, iu, ju] = rank
    R[:, ju, iu] = rank
    b = np.arange(B)
    in_tree = np.zeros((B, d), bool)
    in_tree[:, 0] = True
    best = R[:, 0].copy()
    src = np.zeros((B, d), np.int64)
    adj = np.zeros((B, d, d), bool)
    for _ in range(d - 1):
        v = np.argmax(np.where(in_tree, -2, best), axis=1)
        u = src[b, v]
        adj[b, u, v] = adj[b, v, u] = True
        in_tree[b, v] = True
        row = R[b, v]
        better = row > best
        best = np.where(better, row, best)
        src = np.where(better, v[:, None], src)
    return adj


def structure(x: np.ndarray, n: int, method: str, rate: int,
              counts: str = "int32", codes: dict | None = None,
              lower: tuple = ()) -> np.ndarray:
    u, integer = encode(x, method, rate, codes)
    if "counts" in lower:
        counts = "int16"
    w = weights(gram(u, integer, counts, "grams" in lower), n, method, rate)
    return mwst(bf16(w) if "weights" in lower else w)


def channels(est: np.ndarray, true: np.ndarray) -> np.ndarray:
    """(B, 3) per-trial [error, edge symmetric difference, shared edges]."""
    diff = (est != true).sum(axis=(1, 2)) // 2
    shared = (est & true).sum(axis=(1, 2)) // 2
    return np.stack([diff > 0, diff, shared], axis=1).astype(np.int64)


# --------------------------------------------------------------------------
# a whole sweep
# --------------------------------------------------------------------------

def sweep(d: int, ns, strategies, reps: int, rho_min: float, rho_max: float,
          seed0: int, counts: str = "int32", lower: tuple = (),
          workers: int = 4) -> np.ndarray:
    """(S, len(ns), 3) per-point channel SUMS over the reps trials of one
    sweep: [trials in error, summed edit distance, shared edges].
    ``strategies``: (method, rate) pairs; ``lower``: steps of ``LOWER``
    computed one precision below. Trials run in blocks of at most 2^24
    samples, ``workers`` blocks at a time (numpy releases the GIL)."""
    unknown = set(lower) - set(LOWER)
    if unknown:
        raise ValueError(f"no step {sorted(unknown)} to lower; steps: {LOWER}")
    from concurrent.futures import ThreadPoolExecutor

    parents, rhos = draw_trees(d, reps, rho_min, rho_max, seed0)
    truth = true_adjacency(parents)
    top = max((r for m, r in strategies if m == "persymbol"), default=0)

    def block(i: int, n: int, lo: int, hi: int) -> tuple[int, np.ndarray]:
        z = row_normals(seed0, range(lo, hi), n, d)
        x = sample(z, parents[lo:hi], rhos[lo:hi], "sampling" in lower)
        codes = {top: encode_codes(x, top)} if top else {}
        out = np.zeros((len(strategies), 3), np.int64)
        for s, (method, rate) in enumerate(strategies):
            est = structure(x, n, method, rate, counts, codes, lower)
            out[s] = channels(est, truth[lo:hi]).sum(axis=0)
        return i, out

    jobs = []
    for i, n in enumerate(ns):
        step = max(1, min(reps, (1 << 24) // (n * d)))
        jobs += [(i, n, lo, min(lo + step, reps)) for lo in range(0, reps, step)]
    out = np.zeros((len(strategies), len(ns), 3), np.int64)
    with ThreadPoolExecutor(workers) as pool:
        for i, part in pool.map(lambda j: block(*j), jobs):
            out[:, i] += part
    return out
