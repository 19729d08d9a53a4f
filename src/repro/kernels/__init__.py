"""Pallas TPU kernels for the compute hot spots.

* sign_corr        — quantized-code Gram contraction (paper eq. 8 / eq. 32);
                     rectangular u^T v supported for rowblock placements
* sign_corr_packed — sign Gram straight from 1-bit packed codes: the ±1
                     bit planes are unpacked in VMEM and contracted on the
                     int8 MXU; the wire payload is the compute payload,
                     1 bit/symbol HBM traffic
* code_corr        — per-symbol Gram from int8 bin codes with the centroid
                     decode fused in-kernel (no f32 decode in HBM)
* quantize         — fused per-symbol R-bit encode + centroid decode (eq. 40),
                     optionally emitting the dense packed wire payload too
* decode_attention — flash-decode GQA attention w/ sliding window (serve path)
* flash_prefill    — full-sequence flash attention (train/prefill hot spot)

``repro.core.gram.GramEngine`` is the dispatch layer that routes every Gram
in the repo (estimators / streaming / distributed) onto these kernels.

Each kernel has a pure-jnp oracle in ref.py.
"""
from . import ref  # noqa: F401
