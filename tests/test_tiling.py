"""Large-d engine: (d_tile, d_tile) output streaming, n-chunked
accumulation, pad-target selection, the working-set model, and the
memory-budgeted trial plane.

Integer-exact paths (int8 signs, packed bits) must be BIT-identical under
any tiling — every comparison there is array_equal. Float paths (f32
values, centroid decode) are d-tiled only, so tiles change no per-entry
reduction order; they are still compared allclose out of float caution.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import gram as gram_mod
from repro.core.gram import GramConfig, GramEngine, gram_working_set_bytes
from repro.core.chow_liu import boruvka_mst_batch
from repro.core.experiments import Strategy, TrialPlan, run_trials
from repro.core.glasso import glasso_batch
from repro.core.quantizers import pack_codes
from repro.kernels.sign_corr import PAD_TILES, _d_block, sign_corr

PALLAS = GramEngine(backend="pallas", interpret=True)
XLA = GramEngine(backend="xla")
NUMPY = GramEngine(backend="numpy")


def _signs(n, d, seed):
    rng = np.random.default_rng(seed)
    return rng.choice([-1, 1], size=(n, d)).astype(np.int8)


def _pack(u):
    n = u.shape[0]
    bits = ((u.T + 1) // 2).astype(np.int32)
    bits = np.pad(bits, ((0, 0), (0, (-n) % 8)))
    return jnp.asarray(np.asarray(pack_codes(jnp.asarray(bits), 1)))


def _tiled(eng, d_tile, n_chunk=None):
    return dataclasses.replace(eng, d_tile=d_tile, n_chunk=n_chunk)


# ---------------------------------------------------------------------------
# tiled vs monolithic parity, odd shapes, every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eng,n,d", [
    (PALLAS, 88, 130),   # interpret mode: keep the grid small
    (XLA, 296, 130),
    (NUMPY, 296, 130),
    (XLA, 72, 1025),     # d past eight 128-tiles, odd
    (NUMPY, 72, 1025),
])
@pytest.mark.parametrize("d_tile,n_chunk", [(64, None), (100, 48), (128, 64)])
def test_tiled_gram_bit_identical(eng, n, d, d_tile, n_chunk):
    u = _signs(n, d, seed=n + d)
    want = np.asarray(eng.gram(jnp.asarray(u)))
    got = np.asarray(_tiled(eng, d_tile, n_chunk).gram(jnp.asarray(u)))
    assert np.array_equal(got, want)
    # reference check on one backend-independent ground truth
    exact = u.astype(np.float64).T @ u.astype(np.float64)
    assert np.array_equal(want, exact)


@pytest.mark.parametrize("eng,n,d", [
    (PALLAS, 88, 130), (XLA, 296, 130), (NUMPY, 296, 130),
    (XLA, 72, 1025), (NUMPY, 72, 1025),
])
@pytest.mark.parametrize("d_tile,n_chunk", [(64, None), (100, 48)])
def test_tiled_packed_bit_identical(eng, n, d, d_tile, n_chunk):
    u = _signs(n, d, seed=2 * n + d)
    packed = _pack(u)
    want = np.asarray(eng.packed_sign_gram(packed, n))
    got = np.asarray(
        _tiled(eng, d_tile, n_chunk).packed_sign_gram(packed, n))
    assert np.array_equal(got, want)
    exact = u.astype(np.float64).T @ u.astype(np.float64)
    assert np.array_equal(want, exact)


@pytest.mark.parametrize("eng", [PALLAS, XLA, NUMPY])
def test_tiled_code_and_f32_allclose(eng):
    n, d = 120, 130
    rng = np.random.default_rng(5)
    codes = jnp.asarray(rng.integers(0, 8, size=(n, d)), jnp.int8)
    cb = jnp.linspace(-1.5, 1.5, 8)
    want = np.asarray(eng.code_gram(codes, cb))
    got = np.asarray(_tiled(eng, 64).code_gram(codes, cb))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(_tiled(eng, 64).gram(x)), np.asarray(eng.gram(x)),
        rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("eng", [PALLAS, XLA, NUMPY])
def test_tiled_batch_and_rectangular(eng):
    b, n, dl, dr = 3, 96, 45, 70
    u = np.stack([_signs(n, dl + dr, seed=s) for s in range(b)])
    ul, ur = jnp.asarray(u[..., :dl]), jnp.asarray(u[..., dl:])
    want = np.asarray(eng.gram_batch(ul, ur))
    got = np.asarray(_tiled(eng, 32, 40).gram_batch(ul, ur))
    assert np.array_equal(got, want)
    pb = jnp.stack([_pack(u[i]) for i in range(b)])
    wantp = np.asarray(eng.packed_sign_gram_batch(pb, n))
    gotp = np.asarray(_tiled(eng, 32, 40).packed_sign_gram_batch(pb, n))
    assert np.array_equal(gotp, wantp)


def test_tiled_gram_inside_jit_one_launch_shape():
    # tile assembly is trace-time control flow: under jit it is one program
    eng = _tiled(XLA, 64, 48)
    u = jnp.asarray(_signs(296, 130, seed=9))
    got = jax.jit(eng.gram)(u)
    assert got.shape == (130, 130)
    assert np.array_equal(np.asarray(got), np.asarray(XLA.gram(u)))


# ---------------------------------------------------------------------------
# kernel pad-target selection (the block_d over-padding bugfix)
# ---------------------------------------------------------------------------

def test_d_block_picks_small_pad_tiles():
    # the old behaviour padded every d up to a 128 multiple: d=20 burned
    # 6.4x its lanes. The pad target is now the smallest sufficient tile.
    assert _d_block(20, 256) == 32
    assert _d_block(32, 256) == 32
    assert _d_block(33, 256) == 64
    assert _d_block(100, 256) == 128
    assert _d_block(130, 256) == 256   # past PAD_TILES: 128-multiple
    assert _d_block(1025, 256) == 256  # never above block_d
    assert _d_block(60, 64) == 64      # respects a small block_d
    # a tile narrower than 128 lanes that does not span the output is not
    # a legal TPU block: a small block_d rounds up to the lane tiling
    assert _d_block(100, 64) == 128
    assert _d_block(1024, 64) == 128
    assert tuple(PAD_TILES) == (32, 64, 128)


@pytest.mark.parametrize("n,d", [(40, 20), (88, 130), (24, 33)])
def test_small_d_pad_bit_identity(n, d):
    u = _signs(n, d, seed=d)
    exact = u.astype(np.float64).T @ u.astype(np.float64)
    got = np.asarray(sign_corr(jnp.asarray(u), interpret=True))
    assert np.array_equal(got, exact)


# ---------------------------------------------------------------------------
# memory budget
# ---------------------------------------------------------------------------

def test_memory_budget_needs_a_tpu_limit(monkeypatch):
    """A TPU that reports no bytes_limit is an error, not an assumed 8 GiB;
    a host backend keeps the heuristic."""

    class Dev:
        def __init__(self, platform):
            self.platform, self.device_kind = platform, "test device"

        def memory_stats(self):
            return {}

    monkeypatch.delenv(gram_mod.MEMORY_BUDGET_ENV, raising=False)
    monkeypatch.setattr(gram_mod.jax, "devices", lambda: [Dev("tpu")])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        gram_mod.default_memory_budget()
    monkeypatch.setenv(gram_mod.MEMORY_BUDGET_ENV, str(1 << 30))
    assert gram_mod.default_memory_budget() == 1 << 30
    monkeypatch.delenv(gram_mod.MEMORY_BUDGET_ENV)
    monkeypatch.setattr(gram_mod.jax, "devices", lambda: [Dev("cpu")])
    assert gram_mod.default_memory_budget() == 8 << 30


# d=4096 at n=8192 under a 96 MiB budget: the monolithic xla packed path
# stages an unpack plane over it; tiles fit it, and the plan finds them.
_N4K, _D4K, _BUDGET_4K = 8192, 4096, 96 << 20


def _packed_ws_4k(config=None, backend="xla") -> int:
    return gram_working_set_bytes("packed", _N4K, _D4K, backend=backend,
                                  config=config)


def _budget_config_4k() -> GramConfig:
    """The tiling the plan's budget engine picks (it gives the Gram half
    of the plan's budget)."""
    plan = TrialPlan(d=_D4K, ns=(_N4K,),
                     strategies=(Strategy("sign", wire="packed"),), reps=1,
                     memory_budget_bytes=2 * _BUDGET_4K)
    eng = plan.budget_engine(XLA)
    return GramConfig(d_tile=eng.d_tile, n_chunk=eng.n_chunk)


_LARGE_D_CLAIMS = {
    "monolithic_exceeds_budget": lambda: _packed_ws_4k() > _BUDGET_4K,
    "tiled_within_budget": lambda: _packed_ws_4k(
        GramConfig(d_tile=1024, n_chunk=8192)) <= _BUDGET_4K,
    "budget_engine_within_budget": lambda: _packed_ws_4k(
        _budget_config_4k()) <= _BUDGET_4K,
    # the packed operand is at least 16x lighter than the f32 one
    "packed_operand_leq_16th_f32": lambda: 16 * _packed_ws_4k(
        backend="pallas") <= gram_working_set_bytes(
            "f32", _N4K, _D4K, backend="pallas"),
}


@pytest.mark.parametrize("claim", sorted(_LARGE_D_CLAIMS))
def test_large_d_budget_claims(claim):
    assert _LARGE_D_CLAIMS[claim]()


# ---------------------------------------------------------------------------
# memory-budgeted trial plane
# ---------------------------------------------------------------------------

def _eval_shape_bytes(fn, *args) -> int:
    out = jax.eval_shape(fn, *args)
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(out))


def test_budget_engine_floor_when_nothing_fits():
    # a budget no candidate can honor falls back to the hardest streaming
    # floor rather than refusing to run
    plan = TrialPlan(d=300, ns=(1000,),
                     strategies=(Strategy("sign", wire="packed"),),
                     reps=16, memory_budget_bytes=4 << 20)
    eng = plan.budget_engine(GramEngine(backend="xla"))
    assert (eng.d_tile, eng.n_chunk) == (128, 1024)


def test_budget_engine_fits_declared_budget():
    plan = TrialPlan(d=300, ns=(200, 1000),
                     strategies=(Strategy("sign", wire="packed"),
                                 Strategy("original")),
                     reps=16, memory_budget_bytes=64 << 20)
    eng = plan.budget_engine(GramEngine(backend="xla"))
    assert eng.d_tile is not None  # monolithic would not fit
    n_max = max(plan.bucket_for(n) for n in plan.ns)
    cfg = GramConfig(d_tile=eng.d_tile, n_chunk=eng.n_chunk)
    for path in ("packed", "f32"):
        assert gram_working_set_bytes(
            path, n_max, plan.d, backend="xla", config=cfg,
            batch=plan.reps) <= plan.effective_memory_budget // 2
    # the tiled engine's OUTPUT is unchanged: eval_shape accounting
    u = jax.ShapeDtypeStruct((n_max, plan.d), jnp.int8)
    assert _eval_shape_bytes(eng.gram, u) == 4 * plan.d * plan.d


def test_bucket_backoff_under_budget():
    plan = TrialPlan(d=64, ns=(1030,), strategies=(Strategy("sign"),),
                     reps=32, memory_budget_bytes=2 << 20)
    # pow2 would pad 1030 -> 2048; the budget forces the 8-multiple floor
    assert plan.bucket_for(1030) == 1032
    roomy = dataclasses.replace(plan, memory_budget_bytes=1 << 30)
    assert roomy.bucket_for(1030) == 2048
    # explicit bucket tuples are always respected as given
    pinned = dataclasses.replace(plan, n_buckets=(2048,))
    assert pinned.bucket_for(1030) == 2048


def test_metrics_chunk_under_budget():
    plan = TrialPlan(d=64, ns=(100,), strategies=(Strategy("sign"),),
                     reps=64, memory_budget_bytes=2 << 20)
    chunk = plan.metrics_chunk()
    assert chunk is not None
    assert chunk * 40 * plan.d * plan.d <= plan.effective_memory_budget // 2
    roomy = dataclasses.replace(plan, memory_budget_bytes=1 << 30)
    assert roomy.metrics_chunk() is None


def test_run_trials_budget_metric_identity():
    plan = TrialPlan(d=12, ns=(200, 504),
                     strategies=(Strategy("sign", wire="packed"),
                                 Strategy("original")), reps=6)
    tiny = dataclasses.replace(plan, memory_budget_bytes=150_000)
    full = run_trials(plan)
    small = run_trials(tiny)
    assert small.tiling["memory_budget_bytes"] == 150_000
    assert small.tiling["d_tile"] is not None
    for lab in full.error_rate:
        assert full.error_rate[lab] == small.error_rate[lab]
        assert full.edit_distance[lab] == small.edit_distance[lab]
    assert small.host_syncs == 1


def test_run_trials_tiling_telemetry_default():
    plan = TrialPlan(d=8, ns=(64,), strategies=(Strategy("sign"),), reps=2)
    res = run_trials(plan)
    for key in ("memory_budget_bytes", "d_tile", "n_chunk", "metrics_chunk"):
        assert key in res.tiling


# ---------------------------------------------------------------------------
# chunked metric solvers: bit-parity with the full vmap
# ---------------------------------------------------------------------------

def test_boruvka_batch_chunk_parity():
    rng = np.random.default_rng(17)
    w = rng.normal(size=(11, 9, 9))
    w = jnp.asarray((w + w.transpose(0, 2, 1)) / 2, jnp.float32)
    full = np.asarray(boruvka_mst_batch(w))
    for chunk in (1, 2, 4, 16):
        got = np.asarray(boruvka_mst_batch(w, chunk=chunk))
        assert np.array_equal(got, full)


def test_glasso_batch_chunk_parity():
    rng = np.random.default_rng(23)
    a = rng.normal(size=(7, 30, 6)).astype(np.float32)
    S = jnp.asarray(np.einsum("bnd,bne->bde", a, a) / 30)
    lam = jnp.asarray(np.full(7, 0.1, np.float32))
    full = np.asarray(glasso_batch(S, lam, n_steps=25))
    for chunk in (2, 3, 7):
        got = np.asarray(glasso_batch(S, lam, n_steps=25, chunk=chunk))
        assert np.array_equal(got, full)
