"""Pallas TPU kernels: pairwise-statistic Gram contractions over quantized codes.

The central machine's hot spot (paper §4.2 eq. 8 / §5 eq. 32) is

    G = U^T V,    U, V in {-1,+1}^{n x d}  (sign method)
                  U, V in centroids^{n x d} (per-symbol method)

an n-contraction over all d_l * d_r pairs. Three kernels cover every wire
format the repo uses (see ``repro.core.gram`` for the dispatch layer and the
bytes/symbol table):

* :func:`sign_corr` — int8/low-precision *values* (or anything castable to
  bf16). Tiles the (d_l, d_r) output over a 2-D grid and streams n in
  VMEM-resident blocks, accumulating in f32. The int8 -> bf16 upcast is fused
  in-tile instead of materializing an f32 copy of U in HBM, so HBM traffic is
  1 byte/symbol instead of 4.
* :func:`code_corr` — int8 *bin codes* plus a <=2^R-entry centroid codebook.
  The codebook lives in SMEM and each tile decodes through a chain of L
  2-D selects (no (bn, bd, L) one-hot cube), so the per-symbol Gram
  consumes the wire payload directly: 1 byte/symbol of HBM traffic and no
  decoded copy ever exists in HBM. The decoded f32 tiles contract at
  ``Precision.HIGHEST``, so the result carries f32 accumulation error only
  (no bf16 rounding of the centroids).
* :func:`sign_corr_packed` — uint8 *bit-packed* sign codes (8 symbols/byte,
  the honest 1-bit wire format of ``quantizers.pack_codes``). Uses the
  XNOR+popcount identity: with u in {-1,+1} encoded as bits b,

      sum_i u_j^(i) u_k^(i) = n - 2 * popcount(bits_j XOR bits_k),

  where zero-padded tail bytes cancel exactly (pad bits XOR to 0). HBM
  traffic is 1 *bit*/symbol — 8x under int8, 32x under f32 — and the wire
  payload and the compute payload are the same buffer. The wrapper views
  the bytes as int32 words (4 bytes per lane; the TPU vector unit has no
  8-bit arithmetic) and the kernel runs a SWAR popcount on the words, 8
  output rows at a time, so its XOR intermediate is (8, bd, bw) int32 —
  512 KiB at bd = bw = 128.

Block shapes default to (512, 256) for the MXU kernels: per-step VMEM =
2 * 512*256 B (int8 in) + 2 * 512*256*2 B (bf16 tiles) + 256*256*4 B (acc)
≈ 1.3 MB, comfortably inside v5e's ~16 MB VMEM. Output tiles are either
the whole (padded) d or a multiple of the 128-lane tiling
(:func:`_d_block`), the two shapes the TPU lowering accepts.

Every kernel is TILED over (d_tile, d_tile) OUTPUT blocks with an n-step
accumulation loop as the trailing grid dimension, so per-program VMEM is
bounded by the block shape — never by n or d. What the grid does NOT
bound is the padded HBM footprint: small d pads up to the output-tile
edge. The pad target is picked from :data:`PAD_TILES` (the small end of
the ``core.gram`` autotune candidate set) — the smallest candidate >= d —
instead of a blind 128-multiple: at d=20 the operands pad to 32 lanes
(1.6x), not 128 (>6x wasted lanes). Padded results are bit-identical to
exact shapes (pad rows/lanes contribute exact zeros), pinned by the odd-d
regression tests. For d in the thousands the engine layer
(``core.gram.GramEngine``) additionally streams the OUTER (d, d) product
space tile-by-tile under a memory budget; each streamed tile re-enters
these kernels as a small rectangular Gram.

All three kernels take either a single (n, d) operand or a batch-stacked
(b, n, d) one (packed: (d, nb) / (b, d, nb)). The batch axis is a NATIVE
leading grid dimension — grid (b, i, j, k) with one program per (trial,
output tile, n-step) — not a ``vmap`` of ``pallas_call``, so a whole
Monte-Carlo trial axis (``core.experiments``) runs as ONE kernel launch
and the trial loop never re-enters the dispatch path.

Each ``pallas_call`` passes ``name=`` its public function's name, which
is the kernel's instruction name in the compiled program and its op name
in a device trace; without it the name follows whatever jitted function
encloses the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Output-tile pad candidates for the MXU kernels, shared with the
#: ``core.gram`` autotune layer's d_tile candidate set. Small d pads to the
#: smallest candidate that covers it instead of a blind 128-multiple.
PAD_TILES = (32, 64, 128)


def _d_block(d_max: int, block_d: int) -> int:
    """Output-tile edge for a Gram over d_max features.

    Returns the smallest :data:`PAD_TILES` candidate >= d_max when one fits
    under ``block_d`` (so d=20 pads to 32 lanes, not 128): the tile is then
    the whole padded output. Otherwise the tile is ``block_d`` rounded up
    to the 128-lane tiling, capped at d_max rounded the same way — a tile
    narrower than 128 lanes that does not span the output is not a legal
    TPU block.
    """
    for tile in PAD_TILES:
        if d_max <= tile <= block_d:
            return tile
    return min(_ceil_mult(block_d, 128), _ceil_mult(d_max, 128))


def _as_batched(u: jax.Array) -> tuple[jax.Array, bool]:
    """Promote a single operand to a unit batch; report whether it was 2-D."""
    if u.ndim == 2:
        return u[None], False
    assert u.ndim == 3, u.shape
    return u, True


def _sign_corr_kernel(u_l_ref, u_r_ref, out_ref):
    """Grid (b, d_l/bd, d_r/bd, n/bn); accumulates over the trailing grid dim."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # int8 -> bf16 on the fly; MXU contraction in f32 accumulation
    ul = u_l_ref[0].astype(jnp.bfloat16)  # (bn, bd)
    ur = u_r_ref[0].astype(jnp.bfloat16)  # (bn, bd)
    out_ref[0] += jax.lax.dot_general(
        ul, ur,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def sign_corr(
    u: jax.Array,
    v: jax.Array | None = None,
    *,
    block_n: int = 512,
    block_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """G = u^T v (v defaults to u) with int8/low-precision inputs, f32 accum.

    Args:
      u: (n, d_l) codes — or a batch-stacked (b, n, d_l) — int8 (signs / bin
        indices mapped to centroid ids) or any dtype castable to bf16. n, d
        padded internally to block multiples; the batch axis is a native
        leading grid dimension (one launch for the whole batch).
      v: optional (n, d_r) / (b, n, d_r) right operand for rectangular Grams
        (e.g. the rowblock placement in ``core.distributed``); must share
        u's batch and n.
    Returns:
      (d_l, d_r) — batched: (b, d_l, d_r) — float32 Gram matrix.
    """
    if v is None:
        v = u
    u, batched = _as_batched(u)
    v, _ = _as_batched(v)
    b, n, dl = u.shape
    bv, nv, dr = v.shape
    assert (b, n) == (bv, nv), (u.shape, v.shape)
    bn = min(block_n, _ceil_mult(n, 8))
    bd = _d_block(max(dl, dr), block_d)
    n_p, dl_p, dr_p = _ceil_mult(n, bn), _ceil_mult(dl, bd), _ceil_mult(dr, bd)
    if (n_p, dl_p) != (n, dl):
        u = jnp.pad(u, ((0, 0), (0, n_p - n), (0, dl_p - dl)))
    if (n_p, dr_p) != (nv, dr):
        v = jnp.pad(v, ((0, 0), (0, n_p - nv), (0, dr_p - dr)))
    grid = (b, dl_p // bd, dr_p // bd, n_p // bn)
    out = pl.pallas_call(
        _sign_corr_kernel,
        name="sign_corr",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, bd), lambda a, i, j, k: (a, k, i)),
            pl.BlockSpec((1, bn, bd), lambda a, i, j, k: (a, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bd, bd), lambda a, i, j, k: (a, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, dl_p, dr_p), jnp.float32),
        interpret=interpret,
    )(u, v)
    out = out[:, :dl, :dr]
    return out if batched else out[0]


# ---------------------------------------------------------------------------
# code_corr: Gram over int8 bin codes with in-kernel centroid decode
# ---------------------------------------------------------------------------

def _code_corr_kernel(c_l_ref, c_r_ref, cents_ref, out_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def decode(codes):  # one 2-D select per level; no (bn, bd, L) cube
        c = codes.astype(jnp.int32)
        val = jnp.zeros(c.shape, jnp.float32)
        for level in range(cents_ref.shape[1]):
            val = jnp.where(c == level, cents_ref[0, level], val)
        return val

    out_ref[0] += jax.lax.dot_general(
        decode(c_l_ref[0]), decode(c_r_ref[0]),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def code_corr(
    codes: jax.Array,
    centroids: jax.Array,
    codes_rhs: jax.Array | None = None,
    *,
    block_n: int = 512,
    block_d: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """G = decode(codes)^T decode(codes_rhs) with the decode fused in-kernel.

    Args:
      codes: (n, d_l) — or batch-stacked (b, n, d_l) — int8 bin indices in
        [0, L). Negative codes match no level and decode to 0, so a
        -1 sentinel masks out padded samples (the trial plane's
        valid-length masking under shape bucketing).
      centroids: (L,) codebook (``PerSymbolQuantizer.centroids``), L <= 128;
        shared across the batch.
      codes_rhs: optional (n, d_r) / (b, n, d_r) right operand.
    Returns:
      (d_l, d_r) — batched: (b, d_l, d_r) — float32 Gram of the centroid
      values; the decoded values only ever exist as f32 VMEM tiles (never
      in HBM).
    """
    if codes_rhs is None:
        codes_rhs = codes
    (L,) = centroids.shape
    assert L <= 128, "codebook holds at most 2^7 levels (R <= 7)"
    codes, batched = _as_batched(codes)
    codes_rhs, _ = _as_batched(codes_rhs)
    b, n, dl = codes.shape
    bv, nv, dr = codes_rhs.shape
    assert (b, n) == (bv, nv), (codes.shape, codes_rhs.shape)
    bn = min(block_n, _ceil_mult(n, 8))
    bd = _d_block(max(dl, dr), block_d)
    n_p, dl_p, dr_p = _ceil_mult(n, bn), _ceil_mult(dl, bd), _ceil_mult(dr, bd)
    # pad with -1: it matches no level, so pad samples decode to 0
    # (padding with 0 would decode to centroid c_0 and corrupt the Gram)
    if (n_p, dl_p) != (n, dl):
        codes = jnp.pad(
            codes, ((0, 0), (0, n_p - n), (0, dl_p - dl)), constant_values=-1)
    if (n_p, dr_p) != (nv, dr):
        codes_rhs = jnp.pad(
            codes_rhs, ((0, 0), (0, n_p - nv), (0, dr_p - dr)),
            constant_values=-1)
    cents = centroids.astype(jnp.float32)[None, :]  # (1, L)
    grid = (b, dl_p // bd, dr_p // bd, n_p // bn)
    out = pl.pallas_call(
        _code_corr_kernel,
        name="code_corr",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, bd), lambda a, i, j, k: (a, k, i)),
            pl.BlockSpec((1, bn, bd), lambda a, i, j, k: (a, k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bd, bd), lambda a, i, j, k: (a, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, dl_p, dr_p), jnp.float32),
        interpret=interpret,
    )(codes, codes_rhs, cents)
    out = out[:, :dl, :dr]
    return out if batched else out[0]


# ---------------------------------------------------------------------------
# sign_corr_packed: XNOR + popcount Gram over bit-packed sign codes
# ---------------------------------------------------------------------------

def _popcount32(v: jax.Array) -> jax.Array:
    """SWAR popcount of an int32 array (shift/mask adds on the VPU).

    Arithmetic right shifts are safe: every shift is followed by a mask
    that clears the sign-filled bits, and from the third step on every
    partial sum is non-negative."""
    v = v - ((v >> 1) & 0x55555555)
    v = (v & 0x33333333) + ((v >> 2) & 0x33333333)
    v = (v + (v >> 4)) & 0x0F0F0F0F
    v = v + (v >> 8)
    v = v + (v >> 16)
    return v & 0x3F


def _sign_corr_packed_kernel(a_ref, b_ref, out_ref):
    """Grid (b, d_l/bd, d_r/bd, nw/bw); accumulates XOR popcounts over
    int32 words, 8 output rows per step of the unrolled row loop."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = b_ref[0]  # (bd, bw) int32, feature-major packed words
    for r in range(0, a_ref.shape[1], 8):
        a = a_ref[0, r:r + 8, :]
        diff = _popcount32(a[:, None, :] ^ b[None, :, :])  # (8, bd, bw)
        out_ref[0, r:r + 8, :] += jnp.sum(diff, axis=-1)


@functools.partial(
    jax.jit, static_argnames=("n", "block_d", "block_b", "interpret"))
def sign_corr_packed(
    packed: jax.Array,
    n: int,
    packed_rhs: jax.Array | None = None,
    *,
    block_d: int = 128,
    block_b: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Sign-method Gram G = U^T U directly from bit-packed codes.

    Args:
      packed: (d_l, nb) — or batch-stacked (b, d_l, nb) — uint8, feature-
        major: row j holds feature j's n sign bits packed 8/byte in little
        bit order (``quantizers.pack_codes`` / ``bitpack_signs`` layout,
        i.e. the wire payload itself). Bits beyond ``n`` must agree across
        rows — zeroed, or any shared padding — so they XOR to zero and
        drop out of the identity below.
      n: true number of samples (bits) per row; nb == ceil(n / 8).
      packed_rhs: optional (d_r, nb) / (b, d_r, nb) right operand.
      block_b: bytes per n-step, rounded up to whole 128-lane rows of
        int32 words (512 bytes).
    Returns:
      (d_l, d_r) — batched: (b, d_l, d_r) — float32 Gram, exactly
      n - 2*popcount(xor): integer-exact, identical to ``sign_corr`` on the
      unpacked {-1,+1} codes.
    """
    if packed_rhs is None:
        packed_rhs = packed
    assert packed.dtype == jnp.uint8 and packed_rhs.dtype == jnp.uint8
    packed, batched = _as_batched(packed)
    packed_rhs, _ = _as_batched(packed_rhs)
    b, dl, nb = packed.shape
    bv, dr, nbr = packed_rhs.shape
    assert (b, nb) == (bv, nbr), (packed.shape, packed_rhs.shape)
    bd = _d_block(max(dl, dr), block_d)
    nw = -(-nb // 4)
    bw = min(_ceil_mult(max(block_b // 4, 1), 128), _ceil_mult(nw, 128))
    dl_p, dr_p, nw_p = _ceil_mult(dl, bd), _ceil_mult(dr, bd), _ceil_mult(nw, bw)

    def words(p, d, d_p):
        # zero pad bytes XOR to zero; the popcount sums every bit of a
        # word, so the byte order inside the bitcast never matters
        p = jnp.pad(p, ((0, 0), (0, d_p - d), (0, 4 * nw_p - nb)))
        return jax.lax.bitcast_convert_type(
            p.reshape(b, d_p, nw_p, 4), jnp.int32)

    grid = (b, dl_p // bd, dr_p // bd, nw_p // bw)
    pop = pl.pallas_call(
        _sign_corr_packed_kernel,
        name="sign_corr_packed",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bd, bw), lambda a, i, j, k: (a, i, k)),
            pl.BlockSpec((1, bd, bw), lambda a, i, j, k: (a, j, k)),
        ],
        out_specs=pl.BlockSpec((1, bd, bd), lambda a, i, j, k: (a, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, dl_p, dr_p), jnp.int32),
        interpret=interpret,
    )(words(packed, dl, dl_p), words(packed_rhs, dr, dr_p))
    out = (n - 2 * pop[:, :dl, :dr]).astype(jnp.float32)
    return out if batched else out[0]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
