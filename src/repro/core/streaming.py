"""Streaming (online) statistic estimation — n beyond device memory.

The paper's central statistics are sums over samples (eq. 8, eq. 32), so
the central machine can consume the quantized stream in batches and keep
only the (d, d) Gram accumulator: exact equality with the batch estimator,
O(d^2) state, any n. This is the production ingestion path for the
distributed pipeline (machines transmit per-batch code blocks; the center
folds them in as they arrive).

Every per-batch Gram goes through :class:`repro.core.gram.GramEngine`:

* sign / per-symbol batches enter the kernel as **int8 code blocks** — the
  upcast (sign) or centroid decode (per-symbol) happens inside the kernel
  tile, so no f32 decode of a batch is ever materialized;
* :meth:`update_codes` folds in already-quantized wire blocks directly
  (what the center actually receives);
* :meth:`update_packed` folds in 1-bit packed sign payloads via the
  packed sign Gram — the wire bytes are the compute operand;
* :meth:`update_codes_batch` / :meth:`update_packed_batch` fold a STACK of
  per-machine blocks (the shard-ingestion case: M machines' payloads
  arriving together) through the engine's batched kernel grids
  (``GramEngine.code_gram_batch`` / ``packed_sign_gram_batch``) — ONE
  launch for all machines, summed into the accumulator.

The final estimate (:meth:`weights`) is ``estimators.weights_from_gram``
— the same central-machine math the batch, distributed and trial-plane
paths run, so streaming equals batch exactly on the concatenated stream.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from . import estimators
from .gram import GramEngine, resolve_engine
from .quantizers import PerSymbolQuantizer, sign_codes
from .strategy import Strategy


@dataclasses.dataclass
class StreamingGram:
    """Accumulates G += U_batch^T U_batch and n over quantized batches."""

    d: int
    method: str = "sign"          # sign | persymbol | original
    rate: int = 4
    engine: GramEngine | None = None  # None = process default (core.gram)

    def __post_init__(self):
        self.gram = jnp.zeros((self.d, self.d), jnp.float32)
        self.n = 0
        self._quant = (
            PerSymbolQuantizer(self.rate) if self.method == "persymbol" else None
        )

    @classmethod
    def from_strategy(
        cls,
        d: int,
        strategy: Strategy,
        engine: GramEngine | None = None,
    ) -> "StreamingGram":
        """Build the accumulator for a declarative :class:`Strategy`
        (shared with the batch/distributed/trial pipelines)."""
        return cls(d=d, method=strategy.method, rate=strategy.rate,
                   engine=engine)

    @property
    def _eng(self) -> GramEngine:
        return resolve_engine(self.engine)

    def update(self, x_batch: jax.Array) -> "StreamingGram":
        """Quantize a raw sample batch locally and fold it in. The int8 code
        block feeds the Gram kernel directly (decode fused in-kernel)."""
        assert x_batch.shape[1] == self.d
        if self.method == "sign":
            g = self._eng.gram(sign_codes(x_batch))
        elif self.method == "persymbol":
            codes = self._quant.encode(x_batch).astype(jnp.int8)
            g = self._eng.code_gram(codes, self._quant.centroids)
        else:
            g = self._eng.gram(x_batch)
        self.gram = self.gram + g
        self.n += x_batch.shape[0]
        return self

    def update_codes(self, codes: jax.Array) -> "StreamingGram":
        """Fold in an already-quantized (n_b, d) wire block.

        sign: bits in {0,1} or signs in {-1,+1} (int); per-symbol: bin
        indices in [0, 2^R). Codes go straight into the kernel as int8."""
        assert codes.shape[1] == self.d
        if self.method == "sign":
            g = self._eng.gram(self._codes_pm1(codes))
        elif self.method == "persymbol":
            g = self._eng.code_gram(
                jnp.asarray(codes).astype(jnp.int8), self._quant.centroids)
        else:
            raise ValueError("update_codes requires a quantized method")
        self.gram = self.gram + g
        self.n += codes.shape[0]
        return self

    def update_packed(self, payload: jax.Array, n_batch: int) -> "StreamingGram":
        """Fold in a 1-bit packed sign payload: (d, ceil(n_b/8)) uint8 in
        ``quantizers.pack_codes`` layout (feature-major, little bit order,
        zero tail bits). The packed bytes are contracted directly
        (G_b = n_b - 2*popcount(xor)); nothing is unpacked to HBM."""
        assert self.method == "sign", "packed wire is the sign method"
        assert payload.shape[0] == self.d
        self.gram = self.gram + self._eng.packed_sign_gram(payload, n_batch)
        self.n += n_batch
        return self

    def _codes_pm1(self, codes: jax.Array) -> jax.Array:
        """Accept {0,1} wire bits as well as {-1,+1} signs, as int8."""
        u = jnp.asarray(codes).astype(jnp.int8)
        return jnp.where(u > 0, jnp.int8(1), jnp.int8(-1))

    def update_codes_batch(
        self, codes: jax.Array, n_valid=None
    ) -> "StreamingGram":
        """Fold in a STACK of already-quantized per-machine wire blocks —
        (m, n_b, d) int8 — through ONE batched Gram launch.

        The shard-ingestion path of the distributed pipeline: m machines'
        code blocks arrive together and enter the engine as a native
        kernel grid (``GramEngine.code_gram_batch`` / ``gram_batch``)
        instead of m sequential launches; the per-machine Grams are summed
        into the accumulator. Exactly equals m :meth:`update_codes` calls.

        ``n_valid`` — optional (m,) per-machine delivered-row counts (the
        fault plane's straggler truncation / dropout on HORIZONTAL,
        sample-split machines): machine i contributes only its first
        ``n_valid[i]`` rows (0 = dropped entirely). Rows past the prefix
        are masked before the contraction, so the accumulator equals the
        sequential fold of only the surviving rows, exactly.
        """
        assert codes.ndim == 3 and codes.shape[2] == self.d, codes.shape
        m, n_b, _ = codes.shape
        n_add = m * n_b
        mask = None
        if n_valid is not None:
            nv = jnp.asarray(n_valid, jnp.int32)
            assert nv.shape == (m,), (nv.shape, m)
            mask = jnp.arange(n_b)[None, :, None] < nv[:, None, None]
            n_add = int(np.sum(np.asarray(n_valid)))
        if self.method == "sign":
            u = self._codes_pm1(codes)
            if mask is not None:
                u = jnp.where(mask, u, jnp.int8(0))
            g = self._eng.gram_batch(u)
        elif self.method == "persymbol":
            u = jnp.asarray(codes).astype(jnp.int8)
            if mask is not None:
                from .quantizers import MASKED_CODE

                u = jnp.where(mask, u, jnp.int8(MASKED_CODE))
            g = self._eng.code_gram_batch(u, self._quant.centroids)
        else:
            raise ValueError("update_codes_batch requires a quantized method")
        self.gram = self.gram + jnp.sum(g, axis=0)
        self.n += n_add
        return self

    def update_packed_batch(
        self, payloads: jax.Array, n_batch: int, n_valid=None
    ) -> "StreamingGram":
        """Fold in a STACK of 1-bit packed sign payloads — (m, d,
        ceil(n_b/8)) uint8, one per machine, each encoding ``n_batch``
        samples — via ONE ``packed_sign_gram_batch`` launch (the machine
        axis is a native kernel grid dimension on pallas). The wire bytes
        are the compute operand; nothing is unpacked to HBM. Exactly
        equals m :meth:`update_packed` calls.

        ``n_valid`` — optional (m,) per-machine delivered-row counts
        (prefix truncation; 0 = machine dropped). The truncation is
        applied ON THE WIRE BYTES: each machine's bytes are masked to its
        bit prefix, contracted with the shared popcount kernel, and the
        per-machine Gram corrected by the uniform shift
        ``G_i = n_valid[i] - 2*popcount`` (valid here because a machine's
        truncation is uniform across its d features — horizontal
        placement — unlike the per-feature fault masks of
        ``estimators.payload_gram``). Exactly equals folding each
        machine's surviving prefix alone.
        """
        assert self.method == "sign", "packed wire is the sign method"
        assert payloads.ndim == 3 and payloads.shape[1] == self.d, (
            payloads.shape)
        m = payloads.shape[0]
        if n_valid is None:
            g = self._eng.packed_sign_gram_batch(payloads, n_batch)
            self.gram = self.gram + jnp.sum(g, axis=0)
            self.n += m * n_batch
            return self
        nv = jnp.asarray(n_valid, jnp.int32)
        assert nv.shape == (m,), (nv.shape, m)
        nb = payloads.shape[-1]
        # per-byte bit mask of each machine's surviving prefix: byte j of
        # machine i keeps its low clip(nv[i] - 8j, 0, 8) bits (pack_codes
        # is little-bit-order along the sample axis)
        bits_left = jnp.clip(
            nv[:, None] - 8 * jnp.arange(nb, dtype=jnp.int32)[None, :], 0, 8)
        byte_mask = ((1 << bits_left) - 1).astype(jnp.uint8)  # (m, nb)
        masked = payloads & byte_mask[:, None, :]
        g = self._eng.packed_sign_gram_batch(masked, n_batch)
        # zeroed tail bits xor to 0 (counted as agreement by the kernel's
        # n_batch - 2*popcount); the integer-exact uniform shift restores
        # the true prefix count: G_i = n_valid[i] - 2*popcount
        g = g - (jnp.float32(n_batch)
                 - nv.astype(jnp.float32))[:, None, None]
        self.gram = self.gram + jnp.sum(g, axis=0)
        self.n += int(np.sum(np.asarray(n_valid)))
        return self

    def merge(self, other: "StreamingGram") -> "StreamingGram":
        """Fold ANOTHER accumulator in: G += other.G, n += other.n.

        The distributed-ingest / journal-replay primitive: a shard (or a
        replayed journal segment) accumulates its own ``StreamingGram``
        and the center merges the finished accumulator instead of
        re-folding its blocks. On the integer-exact paths (sign codes and
        packed signs — Gram entries are exact integers in f32 up to 2^24)
        the merge is EXACTLY the fold of the union of both accumulators'
        blocks, in any order. On float-valued paths (per-symbol R >= 2,
        'original') it is the same sum with ``other``'s contribution
        associated as one block — deterministic, and bit-equal to the
        sequential fold whenever ``other`` holds a single block.
        """
        if not isinstance(other, StreamingGram):
            raise TypeError(f"can only merge StreamingGram, got {type(other)}")
        if (self.d, self.method) != (other.d, other.method):
            raise ValueError(
                f"incompatible accumulators: d/method "
                f"{(self.d, self.method)} vs {(other.d, other.method)}")
        if self.method == "persymbol" and self.rate != other.rate:
            raise ValueError(
                f"incompatible per-symbol rates: {self.rate} vs {other.rate}")
        self.gram = self.gram + other.gram
        self.n += other.n
        return self

    def weights(self) -> jax.Array:
        """Chow-Liu weight matrix — identical to the batch estimator on the
        concatenation of every batch seen so far (the shared
        ``estimators.weights_from_gram`` central-machine math)."""
        return estimators.weights_from_gram(self.gram, self.n, self.method)

    def learn_adjacency(self) -> jax.Array:
        """Device-side structure estimate: weights -> Boruvka MWST, no host
        round-trip. Returns the (d, d) bool adjacency as a JAX array."""
        from .chow_liu import boruvka_mst

        return boruvka_mst(self.weights())

    def learn_structure(self, backend: str = "kruskal"):
        from .chow_liu import adjacency_to_edges, kruskal_mst

        if backend == "boruvka":
            # weights feed the device solver directly; edge-list conversion
            # is the explicit host step at the API surface
            return adjacency_to_edges(self.learn_adjacency())
        if backend != "kruskal":
            raise ValueError(f"unknown backend {backend!r}")
        return kruskal_mst(np.asarray(self.weights()))
