"""GramEngine benchmark: bytes-moved and achieved-FLOPs per backend/path.

The repo's hot path is one contraction — G = U^T U over quantized codes —
and its cost is HBM (and wire) traffic, not FLOPs: at (n=65536, d=1024) the
f32 operand is 256 MiB while the 1-bit packed payload is 8 MiB. This
benchmark times every (path, backend) combination the GramEngine dispatches
and reports

  * ``bytes_moved``  — the Gram operand's HBM working set (the wire payload
    for code paths); analytic, platform-independent,
  * ``gflops``       — 2 n d^2 useful FLOPs (the contraction itself),
  * ``gbps`` / ``gflops_per_s`` — achieved from wall time.

The paper-claim check (also the PR acceptance bar): the packed path moves
>= 4x fewer bytes than the f32 baseline at (n=65536, d=1024). (It moves
32x fewer — 4 bytes/symbol vs 1 bit/symbol.)

Timing on CPU runs the xla backend (the pallas kernels interpret on CPU,
which benchmarks the interpreter, not the kernel); on TPU it times the
pallas kernels natively. The acceptance shape's bytes row is always
emitted, even under --quick / when timing at that size is skipped.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.gram import GramEngine
from repro.core.quantizers import PerSymbolQuantizer, pack_codes
from .common import save_artifact

ACCEPTANCE_SHAPE = (65536, 1024)  # (n, d) named in the PR acceptance criteria


def path_bytes(path: str, n: int, d: int) -> int:
    """HBM bytes of the Gram operand (== wire payload for code paths)."""
    return {
        "f32": n * d * 4,      # unquantized baseline
        "int8": n * d,         # sign/per-symbol codes, 1 byte/symbol
        "packed": n * d // 8,  # 1 bit/symbol: wire == compute payload
    }[path]


def _time(fn, reps=3):
    jax.block_until_ready(fn())  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.time() - t0) / reps


def _operands(n, d, seed=0):
    rng = np.random.default_rng(seed)
    u = rng.choice([-1, 1], size=(n, d)).astype(np.int8)
    xf = jnp.asarray(u, jnp.float32)
    xi = jnp.asarray(u)
    bits = jnp.asarray(((u.T + 1) // 2).astype(np.int32))
    packed = pack_codes(bits, 1)  # (d, n/8)
    return xf, xi, packed


def run(quick: bool = False) -> dict:
    on_accel = jax.default_backend() == "tpu"
    backend = "pallas" if on_accel else "xla"
    eng = GramEngine(backend=backend)
    shapes = [(8192, 256)] if quick else [(16384, 512), ACCEPTANCE_SHAPE]

    rows = []
    for n, d in shapes:
        xf, xi, packed = _operands(n, d)
        gflops = 2.0 * n * d * d / 1e9
        paths = {
            "f32": lambda: eng.gram(xf),
            "int8": lambda: eng.gram(xi),
            "packed": lambda: eng.packed_sign_gram(packed, n),
        }
        ref = None
        for path, fn in paths.items():
            t = _time(fn)
            g = np.asarray(fn())
            if ref is None:
                ref = g
            nbytes = path_bytes(path, n, d)
            rows.append({
                "path": path, "backend": backend, "n": n, "d": d,
                "bytes_moved": nbytes,
                "gb_moved": nbytes / 2**30,
                "seconds": t,
                "gbps": nbytes / t / 1e9,
                "gflops": gflops,
                "gflops_per_s": gflops / t,
                "max_err_vs_f32": float(np.abs(g - ref).max()),
            })
            print(f"gram {path:6s} [{backend}] n={n} d={d}: "
                  f"{t*1e3:8.1f} ms  {nbytes/2**20:7.1f} MiB moved  "
                  f"{gflops/t:7.1f} GFLOP/s", flush=True)

    # the acceptance-criteria ratio is analytic — always reported, even when
    # the big shape was not timed (quick mode / slow hosts)
    n_a, d_a = ACCEPTANCE_SHAPE
    ratio = path_bytes("f32", n_a, d_a) / path_bytes("packed", n_a, d_a)
    payload = {
        "rows": rows,
        "acceptance": {
            "shape": {"n": n_a, "d": d_a},
            "f32_bytes": path_bytes("f32", n_a, d_a),
            "packed_bytes": path_bytes("packed", n_a, d_a),
            "bytes_ratio_f32_over_packed": ratio,
        },
        "checks": {
            "packed_moves_4x_fewer_bytes": ratio >= 4.0,
            "paths_agree": all(r["max_err_vs_f32"] == 0.0 for r in rows),
        },
    }
    save_artifact("gram_engine", payload)
    return payload


if __name__ == "__main__":
    run()
