"""Operations and bytes of the Gram statistic, and the chip's peaks.

Work is defined by the statistic, not by the kernel that computes it, so
the same work is counted whatever implements it:

- a Gram of n samples between d_a and d_b features is ``2 n d_a d_b``
  operations at the true n (padding rows are waste, not work);
- its bytes are the wire payload handed to the kernel (1 bit a symbol
  packed, 1 byte a symbol int8, 4 bytes a float32 value) plus the f32
  (d_a, d_b) output;
- sign statistics (int8 or packed) are held to the int8 peak, code and
  value statistics to the bf16 peak;
- the least time is the larger of operations over peak and bytes over
  bandwidth.
"""
from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
#: wire bits per symbol
WIRE_BITS = {"packed": 1, "int8": 8, "float32": 32}


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def gram_ops(n: int, d_a: int, d_b: int | None = None) -> int:
    return 2 * n * d_a * (d_a if d_b is None else d_b)


def gram_bytes(n: int, wire: str, d_a: int, d_b: int | None = None) -> int:
    """Payload read by a Gram (one operand when ``d_b`` is None, the
    symmetric U^T U) plus its f32 output."""
    feats = d_a if d_b is None else d_a + d_b
    per_feature = math.ceil(n * WIRE_BITS[wire] / 8)
    return feats * per_feature + 4 * d_a * (d_a if d_b is None else d_b)


def peak_ops(method: str, peak: dict) -> float:
    return peak["int8_ops"] if method == "sign" else peak["bf16_flops"]


def least_seconds(ops: float, nbytes: float, ops_per_s: float,
                  bytes_per_s: float) -> tuple[float, str]:
    """(least time, which bound binds: "compute" or "memory")."""
    t_ops, t_mem = ops / ops_per_s, nbytes / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def gram_least_seconds(n: int, d: int, method: str, wire: str,
                       peak: dict) -> tuple[float, str]:
    """Least time of one symmetric (d, d) Gram of n samples on one chip."""
    return least_seconds(gram_ops(n, d), gram_bytes(n, wire, d),
                         peak_ops(method, peak), peak["hbm_bytes_per_s"])
