"""GramEngine: one dispatch point for every pairwise-statistic contraction.

Every pipeline in the repo — batch estimators (``core.estimators``), the
streaming accumulator (``core.streaming``), and both compute placements of
the distributed shard_map runtime (``core.distributed``) — reduces to the
same hot spot: the Gram contraction ``G = U^T V`` over quantized codes
(paper §4.2 eq. 8 for the sign method, §5 eq. 32 per-symbol). This module
routes all of them through a single engine with three backends:

* ``pallas``  — the fused TPU kernels in ``repro.kernels.sign_corr``. Codes
  stay in their wire dtype all the way into VMEM (int8 upcast / centroid
  decode / bit-unpack happen per tile); on CPU the kernels run in
  ``interpret=True`` mode so tier-1 tests exercise the exact same code path.
* ``xla``     — pure-jnp contractions, jit-friendly and shard_map-safe: the
  fast path on CPU and the semantic reference on any platform.
* ``numpy``   — host-side reference (returns ``np.ndarray``), used by tests
  and the host-Kruskal path; exact integer arithmetic for sign codes.

``backend="auto"`` (the default engine) resolves to ``pallas`` on TPU and
``xla`` on CPU, overridable with the ``REPRO_GRAM_BACKEND`` env var. Float
contractions on the xla backend run at ``Precision.HIGHEST``: the TPU's
default f32 matmul rounds its operands to bf16.

Three input kinds cover every wire format; HBM/wire bytes per symbol:

  ============  =====================  ==========================  =========
  input kind    entry point            backend compute             bytes/sym
  ============  =====================  ==========================  =========
  f32 values    ``gram(x)``            MXU bf16 / f32 matmul       4
  int8 values   ``gram(u)``            in-tile int8->bf16 matmul   1
  int8 codes    ``code_gram(c, cb)``   in-kernel centroid decode   1
  packed bits   ``packed_sign_gram``   VMEM bit planes, int8 MXU   1/8
  ============  =====================  ==========================  =========

(the xla/numpy backends match each entry point's semantics but may widen
internally — e.g. ``packed_sign_gram`` under xla unpacks to ±1 in registers
before a matmul; only the pallas path keeps the 1-bit working set in HBM.)

Every entry point has a ``*_batch`` twin taking a leading batch axis
((b, n, d) values / codes, (b, d, nb) packed payloads) and returning
(b, d, d). On the pallas backend the batch axis is a native leading grid
dimension of the kernel — one launch for the whole batch, not a ``vmap``
of ``pallas_call``. Two consumers ride it: the trial plane
(``core.experiments.run_trials``) turns a Monte-Carlo trial axis into a
single kernel grid, and the streaming accumulator's shard-ingestion path
(``StreamingGram.update_codes_batch`` / ``update_packed_batch``) folds a
stack of per-machine wire blocks in one launch.

Large-d engine
--------------

At d in the thousands the monolithic per-backend intermediates — the xla
f32 upcast/unpack planes, the numpy XOR cube, the padded kernel operands —
stop fitting a fixed memory budget even though the output (d, d) does. Two
orthogonal engine knobs bound them:

* ``d_tile``: stream the OUTPUT product space in (d_tile, d_tile) blocks;
  each block re-enters the monolithic path on operand slices, so transient
  working set scales with d_tile, not d. d-tiling never changes what is
  computed per entry: integer-exact paths (int8 signs, packed bits) are
  bit-identical to the monolithic result; float paths agree to matmul
  reduction-order noise.
* ``n_chunk``: additionally accumulate integer-exact paths over n- (or
  packed-byte-) chunks. Partial Grams are exact integers (< 2^24 in f32),
  so chunked accumulation is also bit-identical. Float values are never
  n-chunked (that would change the reduction order of the baseline).

The kernel tile edges (``block_n``, ``block_d``, ``block_b``) are fixed
defaults; the packed kernel's come from a tile sweep on a TPU v5e
(PERF.md). ``TrialPlan.budget_engine`` sets ``d_tile``/``n_chunk`` from
the memory budget it observes.

:func:`gram_working_set_bytes` is the shared analytic model of those
transients; ``TrialPlan`` uses it (via :func:`default_memory_budget`) to
pick buckets and tiles that fit the device.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Literal

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.sign_corr import code_corr, sign_corr, sign_corr_packed

Backend = Literal["auto", "pallas", "xla", "numpy"]

#: Env var: override the backend-derived memory budget (bytes).
MEMORY_BUDGET_ENV = "REPRO_MEMORY_BUDGET_BYTES"


@dataclasses.dataclass(frozen=True)
class GramConfig:
    """One resolved tiling configuration for a Gram call.

    ``block_*`` are the pallas kernel tile edges; ``d_tile``/``n_chunk``
    are the engine-level streaming knobs (see module docstring). ``None``
    means monolithic along that axis. The all-defaults instance is the
    engine's historical behaviour.
    """

    block_n: int = 512
    block_d: int = 256
    block_b: int = 1024
    d_tile: int | None = None
    n_chunk: int | None = None


def _spans(size: int, tile: int) -> list[tuple[int, int]]:
    return [(i, min(i + tile, size)) for i in range(0, size, tile)]


def _assemble_tiles(block_fn, dl: int, dr: int, tile: int, xp):
    """Assemble a (.., dl, dr) Gram from (d_tile, d_tile) output blocks."""
    rows = []
    for i0, i1 in _spans(dl, tile):
        row = [block_fn(i0, i1, j0, j1) for j0, j1 in _spans(dr, tile)]
        rows.append(row[0] if len(row) == 1 else xp.concatenate(row, axis=-1))
    return rows[0] if len(rows) == 1 else xp.concatenate(rows, axis=-2)


def _binary_antisymmetric_centroid(centroids) -> float | None:
    """c > 0 when ``centroids`` is a concrete 2-level codebook [-c, +c].

    The rate-1 per-symbol codebook is exactly this shape (equiprobable
    standard-normal bins are symmetric), so its decoded Gram factors as
    c^2 * (sign Gram of the +-1 mapped codes) — an INTEGER contraction.
    ``None`` for traced, non-binary, or asymmetric codebooks.
    """
    if centroids is None or isinstance(centroids, jax.core.Tracer):
        return None
    cb = np.asarray(centroids, dtype=np.float32)
    if cb.shape != (2,) or not (cb[1] > 0.0 and cb[0] == -cb[1]):
        return None
    return float(cb[1])


def _binary_codes_to_signs(codes, xp):
    """{0 -> -1, 1 -> +1, anything else (MASKED_CODE, OOB) -> 0} as int8 —
    the sign-Gram operand of a 2-level codebook, with the same
    masked-code-drops-out semantics as the centroid decode."""
    c = xp.asarray(codes)
    return (c == 1).astype(xp.int8) - (c == 0).astype(xp.int8)


def _to_f32(a, xp):
    if xp is np:
        return np.asarray(a, dtype=np.float32)
    return jnp.asarray(a).astype(jnp.float32)


def _contract_values(uf, vf, batched: bool, xp):
    """u^T v over the sample axis. The unbatched form runs as a batch of
    one through the same contraction, so ``gram(u[i])`` and
    ``gram_batch(u)[i]`` round identically."""
    if not batched:
        return _contract_values(uf[None], vf[None], True, xp)[0]
    if xp is np:
        return np.matmul(np.swapaxes(uf, -1, -2), vf)
    return jax.lax.dot_general(
        uf, vf, (((1,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _contract_planes(uf, vf, batched: bool):
    if batched:
        return jnp.einsum("bdn,ben->bde", uf, vf)
    return uf @ vf.T


@dataclasses.dataclass(frozen=True)
class GramEngine:
    """Backend-dispatched Gram contraction over (quantized) sample matrices.

    Attributes:
      backend: ``auto`` | ``pallas`` | ``xla`` | ``numpy``. ``auto`` resolves
        per-call from ``REPRO_GRAM_BACKEND`` or the default jax backend
        (pallas on TPU, xla elsewhere).
      interpret: Pallas interpret-mode override. ``None`` = interpret iff
        not running on a TPU (so ``backend="pallas"`` is always safe in
        tests).
      block_n / block_d / block_b: kernel tile sizes for the pallas backend.
        ``block_d`` is clamped to 128 for the code kernel (its decoded f32
        tiles grow with block_d) and is not used by the packed kernel,
        whose output tile is its own default (``sign_corr_packed``);
        ``block_b`` is the packed kernel's bytes per n-step.
      d_tile: stream the (d, d) output in (d_tile, d_tile) blocks when d
        exceeds it (``None`` = monolithic). Bit-identical for integer-exact
        paths; bounds every backend's transient working set.
      n_chunk: accumulate integer-exact paths over n-chunks of this many
        samples (packed: ``n_chunk/8``-byte chunks). ``None`` = one pass.
        Never applied to float values (reduction-order stability of the
        unquantized baseline).

    The dataclass stays frozen/hashable: engine instances key the jitted
    stage caches in ``core.experiments``.
    """

    backend: Backend = "auto"
    interpret: bool | None = None
    block_n: int = 512
    block_d: int = 256
    block_b: int = 1024
    d_tile: int | None = None
    n_chunk: int | None = None

    def resolve(self) -> str:
        b = self.backend
        if b == "auto":
            b = os.environ.get("REPRO_GRAM_BACKEND") or (
                "pallas" if jax.default_backend() == "tpu" else "xla")
        if b not in ("pallas", "xla", "numpy"):
            raise ValueError(f"unknown gram backend {b!r}")
        return b

    def _interpret(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() != "tpu"
        return self.interpret

    def _config(self) -> GramConfig:
        return GramConfig(self.block_n, self.block_d, self.block_b,
                          self.d_tile, self.n_chunk)

    def _xp(self, backend: str):
        return np if backend == "numpy" else jnp

    # -- values: f32 / bf16 / int8 ±1 or centroid values --------------------

    def gram(self, u: jax.Array, v: jax.Array | None = None) -> jax.Array:
        """G = u^T v (v defaults to u) over (n, d)-shaped value matrices.

        Integer codes (and bf16) dispatch to the pallas kernel, whose bf16
        MXU tiles represent them exactly. f32/f64 values — the unquantized
        baseline — always contract in f32 (xla path), so the baseline is
        never silently quantized to bf16 by backend selection.
        """
        return self._value_gram(u, v, batched=False)

    def gram_batch(self, u: jax.Array, v: jax.Array | None = None) -> jax.Array:
        """Batched :meth:`gram`: (b, n, d_l) [x (b, n, d_r)] -> (b, d_l, d_r).

        Same dtype dispatch as ``gram``; the pallas path runs the batch as a
        native leading grid dimension of one kernel launch.
        """
        return self._value_gram(u, v, batched=True)

    def _value_gram(self, u, v, *, batched: bool):
        backend = self.resolve()
        ops = (u,) if v is None else (u, v)
        exact_bf16 = all(
            jnp.issubdtype(a.dtype, jnp.integer) or a.dtype == jnp.bfloat16
            for a in ops)
        exact_int = all(jnp.issubdtype(a.dtype, jnp.integer) for a in ops)
        dl, dr = u.shape[-1], ops[-1].shape[-1]
        cfg = self._config()
        block = functools.partial(
            self._value_block, cfg=cfg, backend=backend, batched=batched,
            exact_bf16=exact_bf16, exact_int=exact_int)
        t = cfg.d_tile
        if t is not None and t < max(dl, dr):
            vv = ops[-1]
            return _assemble_tiles(
                lambda i0, i1, j0, j1: block(u[..., i0:i1], vv[..., j0:j1]),
                dl, dr, t, self._xp(backend))
        return block(u, v)

    def _value_block(self, u, v, *, cfg: GramConfig, backend: str,
                     batched: bool, exact_bf16: bool, exact_int: bool):
        if backend == "pallas" and exact_bf16:
            return sign_corr(
                u, v, block_n=cfg.block_n, block_d=cfg.block_d,
                interpret=self._interpret())
        xp = self._xp(backend)
        n = u.shape[-2]
        nc = cfg.n_chunk
        if exact_int and nc is not None and nc < n:
            # partial Grams are exact integers in f32 -> bit-identical
            acc = None
            for k0, k1 in _spans(n, nc):
                uf = _to_f32(u[..., k0:k1, :], xp)
                vf = uf if v is None else _to_f32(v[..., k0:k1, :], xp)
                g = _contract_values(uf, vf, batched, xp)
                acc = g if acc is None else acc + g
            return acc
        uf = _to_f32(u, xp)
        vf = uf if v is None else _to_f32(v, xp)
        return _contract_values(uf, vf, batched, xp)

    # -- int8 bin codes + centroid codebook ---------------------------------

    def code_gram(
        self,
        codes: jax.Array,
        centroids: jax.Array,
        codes_rhs: jax.Array | None = None,
    ) -> jax.Array:
        """Gram of centroid-decoded codes; pallas decodes in-kernel (no f32
        copy of the decode ever reaches HBM), xla/numpy decode then contract.

        Out-of-range codes (the -1 valid-length sentinel of the bucketed
        trial plane) decode to 0 on every backend and drop out of the Gram.
        """
        return self._code_gram(codes, centroids, codes_rhs, batched=False)

    def code_gram_batch(
        self,
        codes: jax.Array,
        centroids: jax.Array,
        codes_rhs: jax.Array | None = None,
    ) -> jax.Array:
        """Batched :meth:`code_gram`: (b, n, d) int8 codes -> (b, d, d).

        The codebook is shared across the batch; the pallas path runs the
        batch as a native leading grid dimension of one launch. -1 codes
        decode to 0 (valid-length masking).
        """
        return self._code_gram(codes, centroids, codes_rhs, batched=True)

    def _code_gram(self, codes, centroids, rhs, *, batched: bool):
        backend = self.resolve()
        c = _binary_antisymmetric_centroid(centroids)
        if c is not None:
            # 2-level antisymmetric codebook (the rate-1 per-symbol path):
            # decode(u) = c * sign(u), so G = c^2 * (integer sign Gram).
            # The sign contraction is integer-exact on every backend, so
            # the R1 code Gram becomes bit-stable under row padding, shape
            # bucketing and batch grouping — the float near-tie that used
            # to flip bucketed-vs-exact MWST metrics at 32x padding came
            # from reduction-order drift of the centroid-decoded f32 sum.
            xp = np if backend == "numpy" else jnp
            u = _binary_codes_to_signs(codes, xp)
            v = None if rhs is None else _binary_codes_to_signs(rhs, xp)
            scale = np.float32(c) * np.float32(c)  # one f32 rounding
            return self._value_gram(u, v, batched=batched) * scale
        dl = codes.shape[-1]
        dr = dl if rhs is None else rhs.shape[-1]
        cfg = self._config()
        t = cfg.d_tile
        if t is not None and t < max(dl, dr):
            rr = codes if rhs is None else rhs
            return _assemble_tiles(
                lambda i0, i1, j0, j1: self._code_block(
                    codes[..., i0:i1], centroids, rr[..., j0:j1],
                    cfg, backend, batched),
                dl, dr, t, self._xp(backend))
        return self._code_block(codes, centroids, rhs, cfg, backend, batched)

    def _code_block(self, codes, centroids, rhs, cfg: GramConfig,
                    backend: str, batched: bool):
        if backend == "pallas":
            return code_corr(
                codes, centroids, rhs,
                block_n=cfg.block_n, block_d=min(cfg.block_d, 128),
                interpret=self._interpret())
        # decode is float-valued: d-tiled only, never n-chunked
        if backend == "numpy":
            uf = self._decode_np(codes, centroids)
            vf = uf if rhs is None else self._decode_np(rhs, centroids)
            return _contract_values(uf, vf, batched, np)
        uf = self._decode_jnp(codes, centroids)
        vf = uf if rhs is None else self._decode_jnp(rhs, centroids)
        return _contract_values(uf, vf, batched, jnp)

    @staticmethod
    def _decode_jnp(codes: jax.Array, centroids: jax.Array) -> jax.Array:
        # out-of-range codes (incl. the -1 mask sentinel) decode to 0.0 —
        # same semantics as the kernel's decode. The bounds check
        # must be explicit: take's own OOB modes normalize negatives first.
        cb = jnp.asarray(centroids, dtype=jnp.float32)
        c = jnp.asarray(codes).astype(jnp.int32)
        in_range = (c >= 0) & (c < cb.shape[0])
        return jnp.where(
            in_range, jnp.take(cb, jnp.clip(c, 0, cb.shape[0] - 1)), 0.0)

    @staticmethod
    def _decode_np(codes, centroids) -> np.ndarray:
        cb = np.asarray(centroids, dtype=np.float32)
        c = np.asarray(codes, dtype=np.int64)
        in_range = (c >= 0) & (c < cb.shape[0])
        return np.where(in_range, cb[np.clip(c, 0, cb.shape[0] - 1)], 0.0)

    # -- 1-bit packed sign codes --------------------------------------------

    def packed_sign_gram(
        self,
        packed: jax.Array,
        n: int,
        packed_rhs: jax.Array | None = None,
    ) -> jax.Array:
        """Sign Gram straight from the packed wire payload.

        ``packed``: (d, ceil(n/8)) uint8, feature-major, little bit order
        (``quantizers.pack_codes`` rate-1 layout); tail bits beyond ``n``
        must be zero. Exact (integer) on every backend:
        G = n - 2*popcount(xor) — pad bits xor to zero and drop out.
        """
        return self._packed_gram(packed, n, packed_rhs, batched=False)

    def packed_sign_gram_batch(
        self,
        packed: jax.Array,
        n: int,
        packed_rhs: jax.Array | None = None,
    ) -> jax.Array:
        """Batched :meth:`packed_sign_gram`: (b, d, ceil(n/8)) -> (b, d, d).

        Per-batch-element bit layout and the n - 2*popcount(xor) identity
        are exactly the unbatched path's; pallas runs the batch as a native
        leading grid dimension of one launch.
        """
        return self._packed_gram(packed, n, packed_rhs, batched=True)

    def _packed_gram(self, packed, n: int, rhs, *, batched: bool):
        if rhs is not None:
            assert packed.shape[-1] == rhs.shape[-1], (
                f"packed operands disagree on byte width: "
                f"{packed.shape} vs {rhs.shape}")
        backend = self.resolve()
        dl = packed.shape[-2]
        dr = dl if rhs is None else rhs.shape[-2]
        cfg = self._config()
        t = cfg.d_tile
        if t is not None and t < max(dl, dr):
            rr = packed if rhs is None else rhs
            return _assemble_tiles(
                lambda i0, i1, j0, j1: self._packed_block(
                    packed[..., i0:i1, :], n, rr[..., j0:j1, :],
                    cfg, backend, batched),
                dl, dr, t, self._xp(backend))
        return self._packed_block(packed, n, rhs, cfg, backend, batched)

    def _packed_block(self, packed, n: int, rhs, cfg: GramConfig,
                      backend: str, batched: bool):
        if backend == "pallas":
            return sign_corr_packed(
                packed, n, rhs, block_b=cfg.block_b,
                interpret=self._interpret())
        nb = packed.shape[-1]
        chunk_b = nb if cfg.n_chunk is None else max(
            1, min(-(-cfg.n_chunk // 8), nb))
        if backend == "numpy":
            a = np.asarray(packed)
            b = a if rhs is None else np.asarray(rhs)
            pop = None  # int64 popcount sums: chunking is bit-identical
            for b0, b1 in _spans(nb, chunk_b):
                p = np.bitwise_count(
                    a[..., :, None, b0:b1] ^ b[..., None, :, b0:b1]).sum(
                        axis=-1, dtype=np.int64)
                pop = p if pop is None else pop + p
            return (n - 2 * pop).astype(np.float32)
        # xla: unpack to ±1 in registers (XLA fuses the unpack into the
        # matmul's operand read); pad bits masked to 0 so they drop out.
        # Chunked unpack keeps the f32 ±1 planes bounded; partial products
        # are exact integers, so the accumulation is bit-identical.
        if chunk_b < nb:
            acc = None
            for b0, b1 in _spans(nb, chunk_b):
                uf = self._unpack_pm1(packed[..., :, b0:b1], n, bit0=8 * b0)
                vf = uf if rhs is None else self._unpack_pm1(
                    rhs[..., :, b0:b1], n, bit0=8 * b0)
                g = _contract_planes(uf, vf, batched)
                acc = g if acc is None else acc + g
            return acc
        uf = self._unpack_pm1(packed, n)
        vf = uf if rhs is None else self._unpack_pm1(rhs, n)
        return _contract_planes(uf, vf, batched)

    @staticmethod
    def _unpack_pm1(packed: jax.Array, n: int, bit0: int = 0) -> jax.Array:
        from .quantizers import bitunpack_signs

        u = bitunpack_signs(packed)  # (..., d, nb*8) ±1 f32
        # bits at absolute position >= n are padding -> 0, drop out of G
        mask = (bit0 + jnp.arange(u.shape[-1])) < n
        return jnp.where(mask, u, 0.0)


# ---------------------------------------------------------------------------
# Analytic working-set model + backend memory budget
# ---------------------------------------------------------------------------

def gram_working_set_bytes(
    path: str,
    n: int,
    d: int,
    *,
    backend: str = "xla",
    config: GramConfig | None = None,
    batch: int = 1,
) -> int:
    """Transient working set (bytes) of one Gram call, operands included,
    EXCLUDING the (d, d) f32 output every path must materialize anyway.

    Counts the operand payload plus the largest intermediate the backend
    stages at HBM/RAM level under ``config``: the xla f32 upcast / decode /
    bit-unpack planes, the numpy XOR-popcount cube. Pallas kernels stage
    only VMEM tiles, so their model is the (padded) operand payload itself.
    The model is deliberately coarse — it drives d_tile/n_chunk selection
    under ``TrialPlan`` memory budgets and the budget tests, not allocator
    bookkeeping.

    path: ``f32`` | ``int8`` | ``code`` | ``packed``.
    """
    if path not in ("f32", "int8", "code", "packed"):
        raise ValueError(f"unknown gram path {path!r}")
    cfg = config or GramConfig()
    t = d if cfg.d_tile is None else min(cfg.d_tile, d)
    if path == "packed":
        nb = -(-n // 8)
        chunk_b = nb if cfg.n_chunk is None else max(
            1, min(-(-cfg.n_chunk // 8), nb))
        oper = batch * d * nb
        if backend == "pallas":
            work = 0
        elif backend == "numpy":
            work = batch * t * t * chunk_b  # uint8 XOR/popcount cube
        else:  # xla: two unpacked ±1 f32 planes per (tile, byte-chunk)
            work = 4 * batch * 2 * t * chunk_b * 8
        return oper + work
    bytes_per = 4 if path == "f32" else 1
    nc = n if cfg.n_chunk is None else min(cfg.n_chunk, n)
    oper = batch * n * d * bytes_per
    if backend == "pallas" or path == "f32":
        # f32 contracts its operands directly; pallas casts in VMEM tiles
        work = 0
    else:
        work = 4 * batch * 2 * nc * t  # f32 upcast/decode of both tile slabs
    return oper + work


def default_memory_budget() -> int:
    """Per-device memory budget in bytes for plan/tile decisions.

    ``REPRO_MEMORY_BUDGET_BYTES`` overrides; else the device's reported
    ``bytes_limit`` (HBM on a TPU). A TPU that reports none is an error —
    plans and tiles sized from a guessed HBM would be wrong on the chip;
    host backends, which report none, get an 8 GiB heuristic.
    """
    env = os.environ.get(MEMORY_BUDGET_ENV)
    if env:
        return int(env)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    limit = int(stats.get("bytes_limit") or 0)
    if limit > 0:
        return limit
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev.device_kind} reports no bytes_limit in memory_stats(); "
            f"set {MEMORY_BUDGET_ENV} to its HBM size in bytes")
    return 8 << 30


# ---------------------------------------------------------------------------
# Default engine: module-level singleton, swappable for experiments/tests
# ---------------------------------------------------------------------------

_default_engine = GramEngine()


def default_engine() -> GramEngine:
    return _default_engine


def set_default_engine(engine: GramEngine) -> GramEngine:
    """Swap the process-wide default engine; returns the previous one."""
    global _default_engine
    prev, _default_engine = _default_engine, engine
    return prev


def resolve_engine(engine: GramEngine | None) -> GramEngine:
    return _default_engine if engine is None else engine
