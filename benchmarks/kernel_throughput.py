"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference timings and
— more importantly on this CPU container — allclose verification at
benchmark shapes + the VMEM working-set accounting for each BlockSpec.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.quantizers import pack_codes
from repro.kernels import ref
from repro.kernels.quantize import quantize_fused
from repro.kernels.sign_corr import sign_corr, sign_corr_packed
from repro.kernels.decode_attention import decode_attention
from .common import save_artifact


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def vmem_working_set() -> dict:
    """Static VMEM accounting per kernel (bytes per grid step)."""
    bn, bd = 512, 256
    sign = 2 * bn * bd * 1 + 2 * bn * bd * 2 + bd * bd * 4
    bm, bnq = 256, 512
    quant = bm * bnq * 4 + bm * bnq * 1 + bm * bnq * 4 + (127 + 128) * 4
    g, dh, bs = 8, 128, 512
    dec = g * dh * 4 + 2 * bs * dh * 4 + g * bs * 4 + g * dh * 4 + 2 * g * 4
    # packed bit planes: two (bd, bb) byte tiles in, two (bd, bb) int8
    # planes and one (bd, bd) int32 dot result live, int32 accumulator out
    pbd, pbb = 512, 1024
    packed = 2 * pbd * pbb + 2 * pbd * pbb + 2 * pbd * pbd * 4
    return {"sign_corr": sign, "sign_corr_packed": packed, "quantize": quant,
            "decode_attention": dec, "vmem_budget": 16 * 2**20}


def run(quick: bool = False) -> dict:
    rows = []
    shapes = [(1024, 128)] if quick else [(1024, 128), (4096, 256)]
    for n, d in shapes:
        u = jnp.asarray(
            np.random.default_rng(0).choice([-1, 1], size=(n, d)), jnp.int8)
        t_k = _time(lambda u: sign_corr(u, interpret=True), u)
        t_r = _time(lambda u: ref.sign_corr_ref(u), u)
        err = float(jnp.abs(sign_corr(u, interpret=True)
                            - ref.sign_corr_ref(u)).max())
        rows.append({"kernel": "sign_corr", "shape": [n, d],
                     "t_interpret": t_k, "t_ref": t_r, "max_err": err})
        print(f"kernel sign_corr {n}x{d}: err={err} "
              f"interp={t_k*1e3:.1f}ms ref={t_r*1e3:.1f}ms", flush=True)

    for n, d in ([(1024, 128)] if quick else [(1024, 128), (4096, 256)]):
        u = np.random.default_rng(1).choice([-1, 1], size=(n, d)).astype(np.int8)
        bits = jnp.asarray(((u.T + 1) // 2).astype(np.int32))
        packed = pack_codes(bits, 1)
        t_k = _time(lambda p: sign_corr_packed(p, n, interpret=True), packed)
        t_r = _time(lambda p: ref.sign_corr_packed_ref(p, n), packed)
        err = float(jnp.abs(sign_corr_packed(packed, n, interpret=True)
                            - ref.sign_corr_ref(jnp.asarray(u))).max())
        rows.append({"kernel": "sign_corr_packed", "shape": [n, d],
                     "t_interpret": t_k, "t_ref": t_r, "max_err": err})
        print(f"kernel sign_corr_packed {n}x{d}: err={err} "
              f"interp={t_k*1e3:.1f}ms ref={t_r*1e3:.1f}ms", flush=True)

    x = jax.random.normal(jax.random.key(0), (512, 256))
    for rate in (1, 4):
        c, v = quantize_fused(x, rate, interpret=True)
        cr, vr = ref.quantize_fused_ref(x, rate)
        rows.append({"kernel": "quantize", "rate": rate,
                     "codes_match": bool(jnp.all(c == cr)),
                     "max_err": float(jnp.abs(v - vr).max())})

    q = jax.random.normal(jax.random.key(1), (2, 16, 128))
    k = jax.random.normal(jax.random.key(2), (2, 2, 1024, 128))
    vv = jax.random.normal(jax.random.key(3), (2, 2, 1024, 128))
    o = decode_attention(q, k, vv, 700, interpret=True)
    orf = ref.decode_attention_ref(q, k, vv, 700)
    rows.append({"kernel": "decode_attention", "shape": [2, 16, 1024, 128],
                 "max_err": float(jnp.abs(o - orf).max())})

    payload = {"rows": rows, "vmem": vmem_working_set()}
    save_artifact("kernel_throughput", payload)
    return payload


if __name__ == "__main__":
    run()
