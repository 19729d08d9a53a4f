"""GramEngine + packed/code Gram kernels: exact parity across backends on
odd (non-block-multiple) shapes, and streaming-vs-batch through the engine.

All pallas paths run interpret=True on this CPU container; sign Grams are
integer-exact so every comparison there is array_equal, not allclose.
"""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.gram import GramEngine, default_engine, set_default_engine
from repro.core.quantizers import PerSymbolQuantizer, pack_codes
from repro.core.streaming import StreamingGram
from repro.kernels.sign_corr import code_corr, sign_corr, sign_corr_packed

PALLAS = GramEngine(backend="pallas", interpret=True)
XLA = GramEngine(backend="xla")
NUMPY = GramEngine(backend="numpy")


def _signs(n, d, seed):
    rng = np.random.default_rng(seed)
    return rng.choice([-1, 1], size=(n, d)).astype(np.int8)


def _pack(u):
    """(n, d) ±1 -> (d, ceil(n/8)) uint8 wire payload, zero tail bits."""
    n = u.shape[0]
    bits = ((u.T + 1) // 2).astype(np.int32)
    bits = np.pad(bits, ((0, 0), (0, (-n) % 8)))
    return jnp.asarray(np.asarray(pack_codes(jnp.asarray(bits), 1)))


# ---------------------------------------------------------------------------
# sign_corr_packed vs sign_corr vs numpy on odd shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [
    (8, 8),        # minimal
    (37, 5),       # tiny, n not a byte multiple
    (100, 30),     # n not a block multiple
    (257, 129),    # both odd, d just past a 128 lane tile
    (300, 257),    # d past two tiles
    (1000, 7),     # byte-ragged n (1000 = 125 bytes exactly), skinny d
    (513, 64),     # n one past a block multiple
    (8192, 31),    # n one whole default n-step (1024 bytes): no pad bits
])
def test_sign_corr_packed_parity_odd_shapes(n, d):
    u = _signs(n, d, seed=n * 1000 + d)
    want = u.astype(np.float64).T @ u.astype(np.float64)
    packed = _pack(u)
    got_packed = np.asarray(sign_corr_packed(packed, n, interpret=True))
    got_dense = np.asarray(sign_corr(jnp.asarray(u), interpret=True))
    assert np.array_equal(got_packed, want), "packed kernel != f32 reference"
    assert np.array_equal(got_dense, want), "dense kernel != f32 reference"
    assert np.array_equal(got_packed, got_dense)


@pytest.mark.parametrize("bd,bb", [(8, 128), (128, 128), (64, 256)])
def test_sign_corr_packed_block_sweep(bd, bb):
    n, d = 203, 45
    u = _signs(n, d, seed=7)
    want = u.astype(np.float64).T @ u.astype(np.float64)
    got = sign_corr_packed(_pack(u), n, block_d=bd, block_b=bb, interpret=True)
    assert np.array_equal(np.asarray(got), want)


def _packed_refs(packed, n, rhs=None):
    """The two exact references: the numpy engine (n - 2*popcount(xor))
    and the unpack-then-contract oracle (pad bits masked to 0)."""
    from repro.kernels.ref import sign_corr_packed_ref

    r = packed if rhs is None else rhs
    got_np = NUMPY.packed_sign_gram(np.asarray(packed), n, np.asarray(r))
    got_ref = np.asarray(sign_corr_packed_ref(packed, n, r))
    assert np.array_equal(got_np, got_ref)
    return got_np


@pytest.mark.parametrize("bd,bb", [
    (512, 1024),   # the defaults: one 384-wide tile, two n-steps
    (128, 512),    # 3x3 output tiles, three n-steps
    (256, 128),    # 2x2 tiles, nine n-steps
])
def test_sign_corr_packed_tiles_at_width(bd, bb):
    """The bit-plane body across output tiles and n-steps at a width past
    one tile: n = 8203 leaves five pad bits in the last byte and pads the
    bytes to whole n-steps."""
    n, d = 8203, 300
    packed = _pack(_signs(n, d, seed=bd + bb))
    got = np.asarray(sign_corr_packed(packed, n, block_d=bd, block_b=bb,
                                      interpret=True))
    assert np.array_equal(got, _packed_refs(packed, n))


def _shared_pad_payload():
    """Zero tail bits replaced by set bits shared by every row: each still
    adds +1 to every entry, which the wrapper subtracts."""
    n, d = 203, 45
    p = np.asarray(_pack(_signs(n, d, seed=5))).copy()
    p[:, -1] |= np.uint8((0xFF << (n % 8)) & 0xFF)
    return n, jnp.asarray(p), None


def _batched_payload():
    rng = np.random.default_rng(12)
    n, d, b = 1000, 37, 3
    u = rng.choice([-1, 1], size=(b, n, d)).astype(np.int8)
    return n, jnp.stack([_pack(u[i]) for i in range(b)]), None


def _rectangular_payload():
    n, dl, dr = 1000, 130, 37  # a 256-row tile beside a 64-lane one
    u = _signs(n, dl + dr, seed=13)
    return n, _pack(u[:, :dl]), _pack(u[:, dl:])


@pytest.mark.parametrize("make", [
    _shared_pad_payload, _batched_payload, _rectangular_payload,
], ids=["shared_pad_bits", "batched", "rectangular"])
def test_sign_corr_packed_matches_references(make):
    n, packed, rhs = make()
    got = np.asarray(sign_corr_packed(packed, n, rhs, interpret=True))
    if packed.ndim == 3:
        want = np.stack([_packed_refs(packed[i], n)
                         for i in range(packed.shape[0])])
    else:
        want = _packed_refs(packed, n, rhs)
    assert np.array_equal(got, want)


def test_sign_corr_packed_rectangular():
    n, dl, dr = 119, 11, 29
    u = _signs(n, dl + dr, seed=11)
    pl_, pr = _pack(u[:, :dl]), _pack(u[:, dl:])
    want = u[:, :dl].astype(np.float64).T @ u[:, dl:].astype(np.float64)
    got = sign_corr_packed(pl_, n, pr, interpret=True)
    assert np.array_equal(np.asarray(got), want)


def test_rectangular_sign_corr():
    n = 150
    u = _signs(n, 37, seed=3)
    v = _signs(n, 130, seed=4)
    want = u.astype(np.float64).T @ v.astype(np.float64)
    got = sign_corr(jnp.asarray(u), jnp.asarray(v), interpret=True)
    assert np.array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# code_corr: in-kernel centroid decode vs decode-then-matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate", [1, 3, 7])
@pytest.mark.parametrize("n,d", [(100, 30), (257, 5), (129, 130)])
def test_code_corr_parity(rate, n, d):
    q = PerSymbolQuantizer(rate)
    x = jax.random.normal(jax.random.key(rate * 100 + n), (n, d))
    codes = q.encode(x).astype(jnp.int8)
    vals = np.asarray(q.decode(q.encode(x)))
    want = vals.T @ vals
    got = np.asarray(code_corr(codes, q.centroids, interpret=True))
    # bf16 MXU tiles: Gram entries are O(n) sums, so the right error scale
    # is absolute-per-sample — bf16 mantissa (2^-8) x O(sqrt n) accumulation
    assert np.abs(got - want).max() / n < 0.01


# ---------------------------------------------------------------------------
# the TPU forms of the packed and code kernels, across output-tile regimes:
# one padded tile (20, 33), two 128-lane tiles (130), many (1025)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [20, 33, 130, 1025])
def test_sign_corr_packed_matches_numpy_engine(d):
    n = 203  # not a byte multiple: zero tail bits
    u = _signs(n, d, seed=d)
    packed = _pack(u)
    got = np.asarray(PALLAS.packed_sign_gram(packed, n))
    assert np.array_equal(got, NUMPY.packed_sign_gram(np.asarray(packed), n))


@pytest.mark.parametrize("d", [20, 33, 130, 1025])
def test_code_corr_within_f32_bound_of_numpy_engine(d):
    """The in-kernel decode contracts f32 tiles at HIGHEST precision, so
    it differs from the numpy engine's f32 Gram only by summation order:
    |G - G_np| <= 2 gamma_(n+1) sum_i |u_ij u_ik| (gamma_m = m u / (1 -
    m u), u = 2^-24), and sum_i |u_ij u_ik| <= sqrt(G_jj G_kk)."""
    n = 200
    q = PerSymbolQuantizer(4)
    x = jax.random.normal(jax.random.key(d), (n, d))
    codes = np.asarray(q.encode(x), np.int8)
    got = np.asarray(PALLAS.code_gram(jnp.asarray(codes), q.centroids_np))
    want = NUMPY.code_gram(codes, q.centroids_np)
    u32 = 2.0 ** -24
    gamma = (n + 1) * u32 / (1 - (n + 1) * u32)
    diag = np.sqrt(np.diagonal(want))
    assert (np.abs(got - want) <= 2 * gamma * np.outer(diag, diag)).all()


# ---------------------------------------------------------------------------
# GramEngine: backend dispatch parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [(100, 13), (257, 33)])
def test_engine_backends_agree_sign(n, d):
    u = _signs(n, d, seed=d)
    want = u.astype(np.float64).T @ u.astype(np.float64)
    packed = _pack(u)
    for eng in (PALLAS, XLA, NUMPY):
        assert np.array_equal(np.asarray(eng.gram(jnp.asarray(u))), want)
        assert np.array_equal(
            np.asarray(eng.packed_sign_gram(packed, n)), want)


@pytest.mark.parametrize("eng", [PALLAS, XLA, NUMPY],
                         ids=["pallas", "xla", "numpy"])
def test_value_int8_and_packed_paths_agree_on_signs(eng):
    """The f32-value, int8 and packed-bit Grams of one ±1 matrix are the
    same integers on every backend."""
    n, d = 1000, 40
    u = _signs(n, d, seed=11)
    g32 = np.asarray(eng.gram(jnp.asarray(u, jnp.float32)))
    assert np.array_equal(g32, np.asarray(eng.gram(jnp.asarray(u))))
    assert np.array_equal(g32, np.asarray(eng.packed_sign_gram(_pack(u), n)))


def test_engine_backends_agree_codes():
    q = PerSymbolQuantizer(4)
    x = jax.random.normal(jax.random.key(0), (150, 21))
    codes = q.encode(x).astype(jnp.int8)
    want = np.asarray(XLA.code_gram(codes, q.centroids))
    got_np = np.asarray(NUMPY.code_gram(np.asarray(codes), q.centroids))
    np.testing.assert_allclose(got_np, want, rtol=1e-6)
    got_pl = np.asarray(PALLAS.code_gram(codes, q.centroids))
    rel = np.abs(got_pl - want) / (np.abs(want) + 1.0)
    assert rel.max() < 0.03


def test_engine_auto_resolution_and_env_override(monkeypatch):
    assert GramEngine().resolve() in ("pallas", "xla")  # platform-dependent
    monkeypatch.setenv("REPRO_GRAM_BACKEND", "numpy")
    assert GramEngine().resolve() == "numpy"
    monkeypatch.delenv("REPRO_GRAM_BACKEND")
    with pytest.raises(ValueError):
        GramEngine(backend="tensorflow").resolve()


def test_import_starts_no_backend():
    """Importing the library takes no device: a process that imports it
    can still hand the chip to a child (one process per chip)."""
    code = ("import repro, repro.core, repro.kernels, repro.serve\n"
            "from jax._src import xla_bridge\n"
            "print(xla_bridge.backends_are_initialized())")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, PYTHONPATH=src))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "False"


def test_set_default_engine_roundtrip():
    prev = set_default_engine(NUMPY)
    try:
        assert default_engine() is NUMPY
    finally:
        set_default_engine(prev)
    assert default_engine() is prev


# ---------------------------------------------------------------------------
# StreamingGram through the engine: batch == stream, all ingestion formats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,rate", [("sign", 1), ("persymbol", 3),
                                         ("original", 1)])
def test_streaming_batch_equality_pallas_interpret(method, rate):
    """Chunked updates through the interpret-mode pallas engine equal the
    one-shot batch Gram (ragged final chunk included)."""
    d, n = 9, 1000
    x = np.asarray(jax.random.normal(jax.random.key(8), (n, d)), np.float32)
    batch = StreamingGram(d=d, method=method, rate=rate, engine=PALLAS)
    batch.update(jnp.asarray(x))
    stream = StreamingGram(d=d, method=method, rate=rate, engine=PALLAS)
    for i in range(0, n, 300):  # 300 does not divide 1000: ragged tail
        stream.update(jnp.asarray(x[i:i + 300]))
    assert stream.n == batch.n == n
    tol = 0 if method == "sign" else 1e-3
    np.testing.assert_allclose(
        np.asarray(stream.gram), np.asarray(batch.gram), atol=tol, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(stream.weights()), np.asarray(batch.weights()),
        atol=1e-5, rtol=1e-4)


def test_streaming_code_and_packed_ingestion_match_raw():
    """update / update_codes / update_packed fold the SAME information: the
    sign Gram is integer-exact across all three wire formats."""
    d, n = 8, 512
    x = np.asarray(jax.random.normal(jax.random.key(9), (n, d)), np.float32)
    u = np.where(x >= 0, 1, -1).astype(np.int8)

    raw = StreamingGram(d=d, method="sign", engine=PALLAS)
    codes = StreamingGram(d=d, method="sign", engine=PALLAS)
    packed = StreamingGram(d=d, method="sign", engine=PALLAS)
    for i in range(0, n, 128):
        xb, ub = x[i:i + 128], u[i:i + 128]
        raw.update(jnp.asarray(xb))
        codes.update_codes(jnp.asarray((ub > 0).astype(np.int8)))  # {0,1} bits
        packed.update_packed(_pack(ub), ub.shape[0])
    assert raw.n == codes.n == packed.n == n
    g = np.asarray(raw.gram)
    assert np.array_equal(g, np.asarray(codes.gram))
    assert np.array_equal(g, np.asarray(packed.gram))
    want = u.astype(np.float64).T @ u.astype(np.float64)
    assert np.array_equal(g, want)


def test_streaming_persymbol_code_ingestion():
    d, n, rate = 6, 400, 3
    q = PerSymbolQuantizer(rate)
    x = jax.random.normal(jax.random.key(10), (n, d))
    via_raw = StreamingGram(d=d, method="persymbol", rate=rate, engine=XLA)
    via_codes = StreamingGram(d=d, method="persymbol", rate=rate, engine=XLA)
    for i in range(0, n, 100):
        via_raw.update(x[i:i + 100])
        via_codes.update_codes(q.encode(x[i:i + 100]).astype(jnp.int8))
    np.testing.assert_allclose(
        np.asarray(via_raw.gram), np.asarray(via_codes.gram), rtol=1e-6)


# ---------------------------------------------------------------------------
# quantize_fused pack=True: fused wire payload
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate", [1, 2, 4])
def test_quantize_fused_pack_matches_pack_codes(rate):
    from repro.kernels.quantize import quantize_fused

    per = 8 // rate
    m, n = 37, 30 * per
    x = jax.random.normal(jax.random.key(rate), (m, n))
    c, v, p = quantize_fused(x, rate, interpret=True, pack=True)
    c2, v2 = quantize_fused(x, rate, interpret=True)
    assert bool(jnp.all(c == c2)) and bool(jnp.all(v == v2))
    want = pack_codes(c.astype(jnp.int32), rate)
    assert p.dtype == jnp.uint8 and p.shape == (m, n * rate // 8)
    assert bool(jnp.all(p == want))


def test_quantize_fused_pack_feeds_packed_gram():
    """End-to-end 1-bit path: fused quantize+pack (feature-major) straight
    into the XNOR+popcount Gram equals the sign Gram of the raw data."""
    from repro.kernels.quantize import quantize_fused

    d, n = 23, 96
    x = np.asarray(jax.random.normal(jax.random.key(12), (n, d)), np.float32)
    _, _, payload = quantize_fused(jnp.asarray(x.T), 1, interpret=True,
                                   pack=True)
    got = np.asarray(sign_corr_packed(payload, n, interpret=True))
    s = np.where(x > 0, 1.0, -1.0)  # rate-1 bin boundary is x > 0
    assert np.array_equal(got, s.T @ s)


# ---------------------------------------------------------------------------
# Batched entry points: the trial axis as a native kernel grid dimension
# ---------------------------------------------------------------------------

def test_gram_batch_matches_per_element():
    rng = np.random.default_rng(7)
    u = rng.choice([-1, 1], size=(3, 100, 17)).astype(np.int8)
    uj = jnp.asarray(u)
    for eng in (PALLAS, XLA):
        got = np.asarray(eng.gram_batch(uj))
        for i in range(3):
            np.testing.assert_array_equal(got[i], np.asarray(eng.gram(uj[i])))
    got_np = NUMPY.gram_batch(u)
    for i in range(3):
        np.testing.assert_array_equal(got_np[i], NUMPY.gram(u[i]))


def test_gram_batch_rectangular_f32():
    # the unbatched f32 Gram runs as a batch of one through the batched
    # contraction, so the two forms round identically: equal, not close
    rng = np.random.default_rng(8)
    u = jnp.asarray(rng.normal(size=(2, 64, 5)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, 9)).astype(np.float32))
    got = np.asarray(XLA.gram_batch(u, v))
    assert got.shape == (2, 5, 9)
    for i in range(2):
        np.testing.assert_array_equal(
            got[i], np.asarray(XLA.gram(u[i], v[i])))


def test_code_gram_batch_matches_and_masks():
    """Batched code Gram == per-element on every backend, and the -1
    valid-length sentinel decodes to 0 (drops out) everywhere."""
    q = PerSymbolQuantizer(3)
    rng = np.random.default_rng(9)
    codes = rng.integers(0, 8, size=(2, 90, 6)).astype(np.int8)
    codes[:, 70:, :] = -1  # masked tail
    cj = jnp.asarray(codes)
    cents = np.asarray(q.centroids)
    # oracle: decode valid codes, zero the masked tail
    dec = np.where(codes >= 0, cents[np.clip(codes, 0, 7)], 0.0)
    want = np.einsum("bnd,bne->bde", dec, dec)
    for eng in (XLA, NUMPY):
        np.testing.assert_allclose(
            np.asarray(eng.code_gram_batch(cj, q.centroids)), want,
            rtol=1e-5, atol=1e-5)
    # pallas decodes to bf16 MXU tiles: per-sample absolute error scale
    got_pl = np.asarray(PALLAS.code_gram_batch(cj, q.centroids))
    assert np.abs(got_pl - want).max() / codes.shape[1] < 0.01


def test_packed_sign_gram_batch_matches():
    rng = np.random.default_rng(10)
    n, d, b = 96, 7, 3
    u = rng.choice([-1, 1], size=(b, n, d)).astype(np.int8)
    payload = jnp.stack([_pack(u[i]) for i in range(b)])  # (b, d, n/8)
    for eng in (PALLAS, XLA, NUMPY):
        got = np.asarray(eng.packed_sign_gram_batch(payload, n))
        for i in range(b):
            want = u[i].T.astype(np.float32) @ u[i].astype(np.float32)
            np.testing.assert_array_equal(got[i], want), (eng.backend, i)


def test_r1_code_gram_bit_stable_under_padding():
    """Regression (trials bench flake): the rate-1 2-level codebook must
    dispatch to the integer sign contraction, so the code Gram is
    BIT-IDENTICAL under 32x row padding with the -1 mask sentinel — the
    float decode path used to change reduction order with the padded
    shape and flip near-tie MWST comparisons."""
    q = PerSymbolQuantizer(1)
    rng = np.random.default_rng(11)
    n, pad, d = 125, 4096, 20
    codes = rng.integers(0, 2, size=(n, d)).astype(np.int8)
    padded = np.full((pad, d), -1, np.int8)
    padded[:n] = codes
    for eng in (PALLAS, XLA, NUMPY):
        a = np.asarray(eng.code_gram(jnp.asarray(codes), q.centroids))
        b = np.asarray(eng.code_gram(jnp.asarray(padded), q.centroids))
        np.testing.assert_array_equal(a, b, err_msg=eng.backend)
        # batching must not change the bits either
        c = np.asarray(eng.code_gram_batch(
            jnp.asarray(padded)[None].repeat(2, 0), q.centroids))
        np.testing.assert_array_equal(a, c[0], err_msg=eng.backend)
        np.testing.assert_array_equal(a, c[1], err_msg=eng.backend)
    # and the dispatch is exact w.r.t. the decode-matmul oracle
    dec = np.where(codes >= 0,
                   np.asarray(q.centroids)[np.clip(codes, 0, 1)], 0.0)
    want = dec.T.astype(np.float64) @ dec.astype(np.float64)
    np.testing.assert_allclose(
        np.asarray(XLA.code_gram(jnp.asarray(codes), q.centroids)),
        want, rtol=1e-6, atol=1e-6)


def test_streaming_merge_exact():
    """StreamingGram.merge: exact union-fold on the integer paths,
    including empty and heterogeneous-ingestion accumulators."""
    rng = np.random.default_rng(12)
    d = 9
    a = StreamingGram(d=d, method="sign", engine=XLA)
    b = StreamingGram(d=d, method="sign", engine=XLA)
    ref = StreamingGram(d=d, method="sign", engine=XLA)
    u1 = rng.choice([-1, 1], size=(40, d)).astype(np.int8)
    u2 = rng.choice([-1, 1], size=(24, d)).astype(np.int8)
    a.update_codes(jnp.asarray(u1))
    b.update_packed(_pack(u2), 24)       # heterogeneous ingestion formats
    ref.update_codes(jnp.asarray(u1))
    ref.update_packed(_pack(u2), 24)
    out = a.merge(b)
    assert out is a and a.n == ref.n == 64
    np.testing.assert_array_equal(np.asarray(a.gram), np.asarray(ref.gram))
    # merging an EMPTY accumulator is the identity, both ways
    before = np.asarray(a.gram).copy()
    a.merge(StreamingGram(d=d, method="sign", engine=XLA))
    np.testing.assert_array_equal(np.asarray(a.gram), before)
    assert a.n == 64
    empty = StreamingGram(d=d, method="sign", engine=XLA)
    empty.merge(ref)
    np.testing.assert_array_equal(np.asarray(empty.gram), before)
    assert empty.n == 64


def test_streaming_merge_validates():
    a = StreamingGram(d=4, method="sign")
    with pytest.raises(TypeError):
        a.merge(np.zeros((4, 4)))
    with pytest.raises(ValueError):
        a.merge(StreamingGram(d=5, method="sign"))
    with pytest.raises(ValueError):
        a.merge(StreamingGram(d=4, method="persymbol", rate=2))
    b = StreamingGram(d=4, method="persymbol", rate=2)
    with pytest.raises(ValueError):
        b.merge(StreamingGram(d=4, method="persymbol", rate=3))
