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
  the honest 1-bit wire format of ``quantizers.pack_codes``). Bit k of
  every byte of a (bd, bb) payload tile is a (bd, bb) plane of ±1 values,
  and the sign Gram is the sum of the eight planes' contractions,

      G = sum_k P_k(A) P_k(B)^T,    P_k(W) = 1 - 2 * ((W >> k) & 1),

  each an int8 "NT" dot with int32 accumulation on the MXU. The samples
  enter the contraction in a permuted order, the same for both operands,
  so G is unchanged. HBM traffic is 1 *bit*/symbol — 8x under int8, 32x
  under f32 — and the wire payload and the compute payload are the same
  buffer: the planes exist only as VMEM values inside the kernel. The
  wrapper lays the bytes of four features into each int32 word; in the
  kernel one shift, mask, multiply and xor turn bit k of all four bytes
  into ±1 bytes, and a bitcast back to int8 puts one feature on each row
  of the plane. Pad bits (the tail of the last byte, and the bytes that
  pad n to whole n-steps) are the same in every row, so each adds exactly
  +1 to every entry, and the wrapper subtracts their count.

Block shapes default to (512, 256) for the MXU kernels: per-step VMEM =
2 * 512*256 B (int8 in) + 2 * 512*256*2 B (bf16 tiles) + 256*256*4 B (acc)
≈ 1.3 MB, comfortably inside v5e's ~16 MB VMEM. The packed kernel's
default is (block_d, block_b) = (512, 1024): per step two 512 KiB payload
tiles in, two (512, 1024) int8 planes and a (512, 512) int32 dot result
live, and the output tile, ≈ 4 MB with double buffering. At d=1024 and
n=65536 it runs within 2% of the fastest tiles measured on a v5e, and
the MXU bounds it. Output tiles are either
the whole (padded) d or a multiple of the 128-lane tiling
(:func:`_d_block`), the two shapes the TPU lowering accepts.

Every kernel is TILED over (d_tile, d_tile) OUTPUT blocks with an n-step
accumulation loop as the trailing grid dimension, so per-program VMEM is
bounded by the block shape — never by n or d. What the grid does NOT
bound is the padded HBM footprint: small d pads up to the output-tile
edge. The pad target is picked from :data:`PAD_TILES` — the smallest
candidate >= d — instead of a blind 128-multiple: at d=20 the operands pad to 32 lanes
(1.6x), not 128 (>6x wasted lanes). Padded results are bit-identical to
exact shapes (pad rows/lanes contribute exact zeros, or for packed codes
a known count that the wrapper subtracts), pinned by the odd-d
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


#: Output-tile pad candidates for the MXU kernels. Small d pads to the
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
# sign_corr_packed: bit-plane Gram over bit-packed sign codes on the MXU
# ---------------------------------------------------------------------------

def _sign_plane(words: jax.Array, k: int) -> jax.Array:
    """Bit k of every byte of ``words`` as a ±1 int8 plane.

    ``words`` is (r, c) int32 whose byte j holds a byte of feature 4i + j,
    so the bitcast to int8 gives (4r, c) with one feature per row. Each
    byte becomes 0x01 (bit clear, +1) or 0xFF (bit set, -1) by SWAR ops
    that never carry across bytes."""
    m = (words >> k) & 0x01010101
    return pltpu.bitcast((m * 0xFE) ^ 0x01010101, jnp.int8)


def _sign_corr_packed_kernel(a_ref, b_ref, out_ref):
    """Grid (b, d_l/bd, d_r/bd, nb/bb); accumulates the contractions of
    the eight ±1 bit planes of each (bd, bb) byte tile over the trailing
    grid dim."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a, b = a_ref[0], b_ref[0]  # (bd/4, bb) int32, four features a word
    acc = None
    for k in range(8):
        g = jax.lax.dot_general(
            _sign_plane(a, k), _sign_plane(b, k),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = g if acc is None else acc + g
    out_ref[0] += acc


@functools.partial(
    jax.jit, static_argnames=("n", "block_d", "block_b", "interpret"))
def sign_corr_packed(
    packed: jax.Array,
    n: int,
    packed_rhs: jax.Array | None = None,
    *,
    block_d: int = 512,
    block_b: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Sign-method Gram G = U^T U directly from bit-packed codes.

    Args:
      packed: (d_l, nb) — or batch-stacked (b, d_l, nb) — uint8, feature-
        major: row j holds feature j's n sign bits packed 8/byte in little
        bit order (``quantizers.pack_codes`` / ``bitpack_signs`` layout,
        i.e. the wire payload itself). Bits beyond ``n`` must agree across
        rows — zeroed, or any shared padding — so each adds exactly +1 to
        every entry and the wrapper subtracts them.
      n: true number of samples (bits) per row; nb == ceil(n / 8).
      packed_rhs: optional (d_r, nb) / (b, d_r, nb) right operand.
      block_d: output-tile edge, per side (:func:`_d_block`).
      block_b: payload bytes per n-step (8 samples each), rounded up to
        whole 128-lane rows.
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
    # one tile edge per side: a rowblock's (d/M, d) Gram pads neither
    # side to the other's tile
    bdl, bdr = _d_block(dl, block_d), _d_block(dr, block_d)
    bb = min(_ceil_mult(max(block_b, 1), 128), _ceil_mult(nb, 128))
    dl_p, dr_p, nb_p = _ceil_mult(dl, bdl), _ceil_mult(dr, bdr), _ceil_mult(nb, bb)

    def words(p, d, d_p):
        # byte j of word (i, c) is byte c of feature 4i + j: the kernel's
        # bitcast back to int8 then puts one feature on each row. Pad
        # bytes are zero in both operands (+1 against +1).
        p = jnp.pad(p, ((0, 0), (0, d_p - d), (0, nb_p - nb)))
        p = p.reshape(b, d_p // 4, 4, nb_p).swapaxes(-1, -2)
        return jax.lax.bitcast_convert_type(p, jnp.int32)

    grid = (b, dl_p // bdl, dr_p // bdr, nb_p // bb)
    acc = pl.pallas_call(
        _sign_corr_packed_kernel,
        name="sign_corr_packed",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bdl // 4, bb), lambda a, i, j, k: (a, i, k)),
            pl.BlockSpec((1, bdr // 4, bb), lambda a, i, j, k: (a, j, k)),
        ],
        out_specs=pl.BlockSpec((1, bdl, bdr), lambda a, i, j, k: (a, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, dl_p, dr_p), jnp.int32),
        interpret=interpret,
    )(words(packed, dl, dl_p), words(packed_rhs, dr, dr_p))
    out = (acc[:, :dl, :dr] - (8 * nb_p - n)).astype(jnp.float32)
    return out if batched else out[0]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
