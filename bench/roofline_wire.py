"""Least times of a structure learned over a mesh of machines.

Work is defined by the statistic and its payload, not by what implements
them, as in ``bench/roofline.py``:

- the wire: the paper's all-gather of ``wire_bytes`` (the payload of all
  M machines). Each machine already holds its own share and receives the
  other M - 1, so the least time is those bytes over one chip's
  interconnect bandwidth (``ici_bits_per_s``);
- the Gram: one (d, d) Gram of n samples per structure, counted once
  however many chips compute it, with the peaks of all the cell's chips.
  Redundant work, such as the replicated placement's full Gram on every
  chip, is waste, so no placement can read over its roofline.
"""
from __future__ import annotations

from bench import roofline


def received_bytes(wire_bytes: int, machines: int) -> float:
    """Bytes each machine receives in the all-gather of ``wire_bytes``
    split evenly over ``machines``."""
    return wire_bytes * (machines - 1) / machines


def wire_least_seconds(wire_bytes: int, machines: int, peak: dict) -> float:
    return received_bytes(wire_bytes, machines) / (peak["ici_bits_per_s"] / 8)


def gram_least_seconds(n: int, d: int, method: str, wire: str, peak: dict,
                       chips: int) -> float:
    """Least time of one symmetric (d, d) Gram of n samples over
    ``chips`` chips."""
    t, _ = roofline.gram_least_seconds(n, d, method, wire, peak)
    return t / chips
