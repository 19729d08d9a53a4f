"""Jit'd public wrappers for the Pallas kernels.

On CPU the kernels execute in ``interpret=True`` mode; on TPU they compile
natively. ``interpret=None`` resolves per call from the default backend,
so importing this module starts no backend.
"""
from __future__ import annotations

import jax

from .decode_attention import decode_attention as _decode_attention
from .flash_prefill import flash_prefill as _flash_prefill
from .quantize import quantize_fused as _quantize_fused
from .sign_corr import code_corr as _code_corr
from .sign_corr import sign_corr as _sign_corr
from .sign_corr import sign_corr_packed as _sign_corr_packed


def _interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def sign_corr(u, v=None, *, block_n: int = 512, block_d: int = 256,
              interpret: bool | None = None):
    return _sign_corr(
        u, v,
        block_n=block_n,
        block_d=block_d,
        interpret=_interpret(interpret),
    )


def code_corr(codes, centroids, codes_rhs=None, *,
              interpret: bool | None = None, **kw):
    return _code_corr(
        codes, centroids, codes_rhs, interpret=_interpret(interpret), **kw)


def sign_corr_packed(packed, n, packed_rhs=None, *,
                     interpret: bool | None = None, **kw):
    return _sign_corr_packed(
        packed, n, packed_rhs, interpret=_interpret(interpret), **kw)


def quantize_fused(x, rate: int, *, interpret: bool | None = None, **kw):
    return _quantize_fused(x, rate, interpret=_interpret(interpret), **kw)


def decode_attention(q, k, v, pos, *, window=None, interpret: bool | None = None, **kw):
    return _decode_attention(
        q, k, v, pos,
        window=window,
        interpret=_interpret(interpret),
        **kw,
    )


def flash_prefill(q, k, v, *, causal=True, window=0,
                  interpret: bool | None = None, **kw):
    return _flash_prefill(
        q, k, v, causal=causal, window=window,
        interpret=_interpret(interpret), **kw)
