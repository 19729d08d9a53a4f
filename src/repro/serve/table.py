"""TenantTable: many StreamingGram accumulators behind batched launches.

Multi-tenant center state, stacked on a leading tenant axis:

* ``gram`` — (T, d, d) float64 host accumulators. Sign and packed-sign
  payload Grams are exact integers (f32-exact out of the kernels, then
  added in float64, exact to 2^53): bit-identical under ANY fold order,
  which is what makes crash replay and merge exact. Rate-1 per-symbol
  Grams are c^2 * integer (``gram.GramEngine`` dispatches the 2-level
  codebook to the sign contraction) — each value carries <= 48 mantissa
  bits, so float64 accumulation is exact there too. Higher-rate
  per-symbol Grams are float-valued; their accumulation is deterministic
  (canonical payload padding + acceptance-order adds) rather than
  order-free.
* ``n`` — (T,) int64 folded sample counts: the per-tenant effective
  count. Lost payloads simply never fold, so
  ``estimators.weights_from_gram`` normalizes by what actually arrived —
  the PR-6 n_eff degradation specialized to sample-split machines.

Every fold tick runs ONE batched device launch per payload kind (codes /
packed) regardless of how many tenants have data: payloads are padded to
the canonical ``(slots, block_n, d)`` shape (slots bucketed to powers of
two) and contracted by ``GramEngine.gram_batch`` /
``code_gram_batch`` / ``packed_sign_gram_batch``; per-slot Grams are
scattered into the tenant stack on the host. Compiled stages are cached
per (kind, slot bucket) — no per-tenant compiles, ever.

Structure is re-solved INCREMENTALLY: only tenants whose accumulator
changed materially since their last solve (or whose watchdog fired) go
through the batched weights -> Boruvka launch, and each solve updates a
structure-drift counter (edge symmetric difference vs. the previous
solve — the hamming channel of
``experiments.structure_metric_channels``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core import estimators, experiments
from ..core.chow_liu import boruvka_mst_batch
from ..core.gram import GramEngine, resolve_engine
from ..core.quantizers import MASKED_CODE, PerSymbolQuantizer
from ..core.streaming import StreamingGram
from .ingest import Payload, split_kinds


def _next_pow2(k: int) -> int:
    return 1 << max(0, (k - 1).bit_length())


def _per_slot(fn, mesh):
    """``fn`` over the slot axis, each device contracting its own slots
    when a tenant mesh is given: the Pallas Gram kernels cannot be
    partitioned automatically, and every slot's Gram is independent, so
    the sharded result is the unsharded one bit for bit."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec

    return jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec("tenant"),
                         out_specs=PartitionSpec("tenant"), check_vma=False)


@functools.lru_cache(maxsize=None)
def _codes_fold_stage(slots: int, block_n: int, d: int, method: str,
                      rate: int, engine: GramEngine, mesh=None):
    """jit: (slots, block_n, d) int8 -> (slots, d, d) f32 per-slot Grams.

    Sign codes arrive as {-1, 0, +1} (0 — a padded row or a masked wire
    entry — drops out of the integer contraction; ``bits=True`` {0,1}
    wires were already mapped to ±1 on the host); per-symbol codes as
    bin indices with MASKED_CODE padding (decodes to 0 on every
    backend). One compile per (kind, slot bucket) serves every tick at
    that bucket. ``mesh``: the tenant mesh the batch is sharded over.
    """
    if method == "sign":
        fn = engine.gram_batch
    elif method == "persymbol":
        centroids = PerSymbolQuantizer(rate).centroids
        fn = functools.partial(engine.code_gram_batch, centroids=centroids)
    else:
        raise ValueError(f"serve folds quantized payloads, got {method!r}")
    return jax.jit(_per_slot(fn, mesh))


@functools.lru_cache(maxsize=None)
def _packed_fold_stage(slots: int, block_n: int, d: int,
                       engine: GramEngine, mesh=None):
    """jit: (slots, d, block_n/8) uint8 + (slots,) valid counts ->
    (slots, d, d) f32. Zero-padded tail bits count as agreement under the
    packed sign kernel; the integer-exact uniform shift
    ``G_i = n_valid[i] - 2*popcount`` restores the true prefix Gram (the
    same identity as ``StreamingGram.update_packed_batch``) — an all-zero
    padding slot lands exactly on 0.
    """
    gram = _per_slot(
        lambda batch: engine.packed_sign_gram_batch(batch, block_n), mesh)

    def f(batch, n_valid):
        return gram(batch) - (jnp.float32(block_n)
                              - n_valid.astype(jnp.float32))[:, None, None]

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _solve_stage(slots: int, d: int, method: str):
    """jit: (slots, d, d) f32 NORMALIZED Grams (gram / max(n, 1),
    divided on the host in float64 — int64 counts round in f32 past 2^24
    samples, a real horizon for accumulators designed to grow forever) +
    (slots,) counts + previous adjacencies -> (new adjacencies,
    [changed, drift, shared] channels).

    ``n`` enters ``weights_from_gram(..., normalized=True)`` as a
    (slots, 1, 1) effective-count operand used only for the persymbol
    bias correction and the n_eff < 2 neutralization (both f32-rounding
    insensitive), so tenants with fewer than 2 folded samples neutralize
    to zero weights instead of NaN — the degraded-tenant solve stays
    finite. The drift channels are the trial plane's integer-exact
    ``structure_metric_channels`` against the PREVIOUS solve.
    """
    def f(stat, n, prev_adj):
        w = estimators.weights_from_gram(stat, n[:, None, None], method,
                                         normalized=True)
        adj = boruvka_mst_batch(w)
        return adj, experiments.structure_metric_channels(adj, prev_adj)

    return jax.jit(f)


@dataclasses.dataclass
class TenantTable:
    """The accumulator stack + incremental-solve state for T tenants."""

    tenants: int
    d: int
    method: str = "sign"
    rate: int = 1
    block_n: int = 64       # canonical payload row bucket (n <= block_n)
    max_slots: int = 64     # largest single fold launch
    engine: GramEngine | None = None
    mesh: object | None = None  # optional ("tenant",) mesh for the solve
    resolve_min_new: int = 1    # new samples before a re-solve
    resolve_fraction: float = 0.0  # ... or this fraction of solved_n

    def __post_init__(self):
        if self.method == "sign":
            self.rate = 1
        if self.block_n % 8:
            raise ValueError("block_n must be a multiple of 8 (packed wire)")
        T, d = self.tenants, self.d
        self.gram = np.zeros((T, d, d), np.float64)
        self.n = np.zeros(T, np.int64)
        self.adj = np.zeros((T, d, d), bool)
        self.solved_n = np.zeros(T, np.int64)
        self.solves = np.zeros(T, np.int64)
        self.drift = np.zeros(T, np.int64)
        self._eng = resolve_engine(self.engine)

    # -- folding ------------------------------------------------------------

    def fold(self, payloads: Sequence[Payload]) -> int:
        """Fold one batch of ACCEPTED payloads (the tick's admissions, in
        acceptance order) through batched launches; returns rows folded.

        The canonical grouping — codes first, then packed, each chunked
        to ``max_slots`` — is shared with journal replay, so a replayed
        batch reproduces the live accumulation order exactly.
        """
        rows = 0
        codes, packed = split_kinds(payloads)
        for chunk in _chunks(codes, self.max_slots):
            rows += self._fold_codes(chunk)
        for chunk in _chunks(packed, self.max_slots):
            rows += self._fold_packed(chunk)
        return rows

    def _fold_codes(self, chunk: list[Payload]) -> int:
        S = _next_pow2(len(chunk))
        fill = 0 if self.method == "sign" else MASKED_CODE
        batch = np.full((S, self.block_n, self.d), fill, np.int8)
        for i, p in enumerate(chunk):
            self._check(p)
            c = p.codes
            if p.bits:
                # {0,1} wire bits -> ±1 (0 is a true -1 on a bit wire)
                c = (2 * c.astype(np.int8) - 1).astype(np.int8)
            # sign values {-1,0,+1} pass through: 0 = masked entry,
            # drops out of the contraction exactly like padding rows
            batch[i, :p.n] = c
        stage = _codes_fold_stage(S, self.block_n, self.d, self.method,
                                  self.rate, self._eng, self._slot_mesh(S))
        g = np.asarray(stage(self._place(batch)), np.float64)
        return self._scatter(chunk, g)

    def _fold_packed(self, chunk: list[Payload]) -> int:
        if self.method != "sign":
            raise ValueError("packed payloads are the sign method")
        S = _next_pow2(len(chunk))
        nb = self.block_n // 8
        batch = np.zeros((S, self.d, nb), np.uint8)
        n_valid = np.zeros(S, np.int32)
        for i, p in enumerate(chunk):
            self._check(p)
            batch[i, :, :p.packed.shape[1]] = p.packed
            n_valid[i] = p.n
        stage = _packed_fold_stage(S, self.block_n, self.d, self._eng,
                                   self._slot_mesh(S))
        g = np.asarray(stage(self._place(batch), jnp.asarray(n_valid)),
                       np.float64)
        return self._scatter(chunk, g)

    def _scatter(self, chunk: list[Payload], g: np.ndarray) -> int:
        rows = 0
        for i, p in enumerate(chunk):  # acceptance order: deterministic
            self.gram[p.tenant] += g[i]
            self.n[p.tenant] += p.n
            rows += p.n
        return rows

    def _check(self, p: Payload) -> None:
        if p.d != self.d:
            raise ValueError(f"payload d={p.d} vs table d={self.d}")
        if not 0 < p.n <= self.block_n:
            raise ValueError(
                f"payload rows {p.n} exceed block_n={self.block_n}")
        if not 0 <= p.tenant < self.tenants:
            raise ValueError(f"unknown tenant {p.tenant}")
        if p.kind != "codes":
            return
        if self.method == "sign":
            lo, hi = (0, 1) if p.bits else (-1, 1)
            if p.codes.min() < lo or p.codes.max() > hi:
                raise ValueError(
                    f"sign payload codes must lie in [{lo}, {hi}] "
                    f"({'wire bits' if p.bits else 'signs, 0 = masked'}), "
                    f"got [{p.codes.min()}, {p.codes.max()}]")
        elif p.bits:
            raise ValueError("bits payloads are the sign method")

    # -- incremental solve --------------------------------------------------

    def needs_resolve(self) -> np.ndarray:
        """(T,) bool — tenants whose Gram changed materially since their
        last solve: at least ``resolve_min_new`` new samples, or
        ``resolve_fraction`` of the count last solved at."""
        fresh = self.n - self.solved_n
        floor = np.maximum(self.resolve_min_new,
                           (self.resolve_fraction
                            * self.solved_n).astype(np.int64))
        return (self.n > 0) & (fresh >= np.maximum(floor, 1))

    def resolve(self, idx: np.ndarray) -> dict:
        """Re-solve structure for the tenant indices ``idx`` (one batched
        weights -> Boruvka launch per pow2 slot bucket) and update the
        drift telemetry. Returns {solved, drifted, drift_edges}."""
        idx = np.asarray(idx, np.int64)
        solved = drifted = drift_edges = 0
        for lo in range(0, len(idx), self.max_slots):
            part = idx[lo:lo + self.max_slots]
            S = _next_pow2(len(part))
            stat = np.zeros((S, self.d, self.d), np.float32)
            n = np.zeros(S, np.float32)
            prev = np.zeros((S, self.d, self.d), bool)
            # normalize in float64 on the host: int64 counts round in
            # f32 beyond 2^24 folded samples, skewing every weight
            safe_n = np.maximum(self.n[part], 1).astype(np.float64)
            stat[:len(part)] = (
                self.gram[part] / safe_n[:, None, None]).astype(np.float32)
            n[:len(part)] = self.n[part]
            prev[:len(part)] = self.adj[part]
            stage = _solve_stage(S, self.d, self.method)
            adj, ch = stage(self._place(stat), jnp.asarray(n),
                            self._place(prev))
            adj = np.asarray(adj)[:len(part)]
            ch = np.asarray(ch)[:len(part)]
            ham = ch[:, 1].astype(np.int64)
            self.adj[part] = adj
            self.drift[part] += ham
            self.solves[part] += 1
            self.solved_n[part] = self.n[part]
            solved += len(part)
            drifted += int((ham > 0).sum())
            drift_edges += int(ham.sum())
        return {"solved": solved, "drifted": drifted,
                "drift_edges": drift_edges}

    def _slot_mesh(self, slots: int):
        """The tenant mesh when one is attached and divides the slot
        bucket (slot buckets are powers of two, and so is the mesh — see
        ``launch.mesh.make_tenant_mesh``); else None."""
        mesh = self.mesh
        if (mesh is not None and mesh.devices.size > 1
                and slots % mesh.devices.size == 0):
            return mesh
        return None

    def _place(self, arr: np.ndarray):
        """Host batch -> device, sharded over :meth:`_slot_mesh`."""
        x = jnp.asarray(arr)
        mesh = self._slot_mesh(arr.shape[0])
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            x = jax.device_put(
                x, NamedSharding(mesh, PartitionSpec("tenant")))
        return x

    # -- state / interop ----------------------------------------------------

    def state_tree(self) -> dict:
        """The snapshot pytree (host numpy leaves; see checkpoint.ckpt)."""
        return {"gram": self.gram, "n": self.n, "adj": self.adj,
                "solved_n": self.solved_n, "solves": self.solves,
                "drift": self.drift}

    def load_state(self, tree: dict) -> None:
        for k, v in self.state_tree().items():
            got = np.asarray(tree[k], v.dtype)
            if got.shape != v.shape:
                raise ValueError(f"snapshot leaf {k}: {got.shape} vs "
                                 f"{v.shape}")
            v[...] = got

    def to_streaming(self, tenant: int) -> StreamingGram:
        """Export one tenant's accumulator as a ``StreamingGram`` (same
        estimator tail; ``StreamingGram.merge`` recombines exports)."""
        sg = StreamingGram(d=self.d, method=self.method, rate=self.rate,
                           engine=self.engine)
        sg.gram = jnp.asarray(self.gram[tenant].astype(np.float32))
        sg.n = int(self.n[tenant])
        return sg


def _chunks(items: list, size: int):
    for lo in range(0, len(items), size):
        yield items[lo:lo + size]
