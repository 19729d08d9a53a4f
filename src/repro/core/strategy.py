"""Declarative estimation strategy: the front door of every pipeline.

A :class:`Strategy` pins down one point of the paper's design space —
quantization method x bit rate x wire format x compute placement x MWST
solver — as a single frozen, hashable value. The batch estimators
(``core.estimators``), the streaming accumulator (``core.streaming``), the
distributed shard_map runtime (``core.distributed``), the centralized
Chow-Liu pipeline (``core.chow_liu``) and the vmapped trial engine
(``core.experiments``) all accept the same object, replacing the loose
``(method, rate, wire, compute)`` kwarg tuples that used to be threaded
through each layer separately.

Being frozen + hashable, a Strategy can key jit caches and result tables
directly; ``label`` matches the paper-figure legend names ("sign",
"R1".."R7", "original").

Strategy is one of three frozen plan values the pipelines compose:
Strategy (WHAT to estimate and how to quantize it),
``core.distributed.WirePlan`` (WHERE each stage runs and which collective
carries the payload), and ``core.faults.FaultPlan`` (what can go WRONG on
that wire — deterministic dropout / straggling / bit-flips with
masked-Gram degradation). All three are hashable for the same reason: they
key the sweep engine's jit caches directly.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

# channel plan values live in the comm layer (no repro imports at their
# module level, so this import is cycle-safe mid-core-init)
from repro.comm.channel import GATHER, Channel

Method = Literal["sign", "persymbol", "original"]
Wire = Literal["int8", "packed", "float32"]
Placement = Literal["replicated", "rowblock"]
Mst = Literal["boruvka", "kruskal"]
Structure = Literal["tree", "sparse"]

_METHODS = ("sign", "persymbol", "original")
_WIRES = ("int8", "packed", "float32")
_PLACEMENTS = ("replicated", "rowblock")
_MSTS = ("boruvka", "kruskal")
_STRUCTURES = ("tree", "sparse")


@dataclasses.dataclass(frozen=True)
class Strategy:
    """One point of the method x rate x wire x placement x mst design space.

    Attributes:
      method: 'sign' (1-bit signs, §4) | 'persymbol' (R-bit quantizer, §5)
        | 'original' (unquantized baseline, eq. 1).
      rate: bits per symbol for 'persymbol' (1..7 on an int8 wire; must
        divide 8 for a packed wire). Forced to 1 for 'sign'.
      wire: transmitted format — 'int8' (one byte per code), 'packed'
        (dense R bits/symbol, the paper's budget), 'float32' (raw samples;
        forced for 'original').
      placement: distributed Gram placement — 'replicated' (collective-
        minimal) or 'rowblock' (each rank computes d/M rows).
      mst: central MWST solver — 'boruvka' (on-device, jit/vmap-able) or
        'kruskal' (host reference). Both break ties identically.
      structure: what the central machine solves for — 'tree' (Chow-Liu
        MWST, the paper's main line) or 'sparse' (graphical lasso over the
        quantized statistics, the §7 extension: the central estimate is a
        sparse precision matrix and recovery is support recovery).
      lam: l1 penalty of the glasso solve (sparse structures only; must
        be > 0 there and 0.0 — the default — for trees, so a forgotten
        ``structure="sparse"`` fails loudly instead of silently running
        the tree pipeline).
      channel: the wire's channel model (``repro.comm.channel``) — the
        default :class:`~repro.comm.channel.GatherChannel` is the paper's
        lossless all-gather (bit-identical to the pre-channel engine);
        :class:`~repro.comm.channel.MACChannel` superposes machine
        sign-Grams (sign method, int8 wire only);
        :class:`~repro.comm.channel.BudgetChannel` allocates heterogeneous
        per-machine rates under a total bit budget (persymbol method,
        int8 wire; ``rate`` is the per-machine cap).
    """

    method: Method = "sign"
    rate: int = 1
    wire: Wire = "int8"
    placement: Placement = "replicated"
    mst: Mst = "boruvka"
    structure: Structure = "tree"
    lam: float = 0.0
    channel: Channel = GATHER

    def __post_init__(self):
        if self.method not in _METHODS:
            raise ValueError(f"unknown method {self.method!r}")
        if self.structure not in _STRUCTURES:
            raise ValueError(f"unknown structure {self.structure!r}")
        if self.structure == "sparse":
            if not self.lam > 0.0:
                raise ValueError(
                    f"sparse structures need a glasso penalty lam > 0, "
                    f"got {self.lam!r}")
            object.__setattr__(self, "lam", float(self.lam))
        elif self.lam != 0.0:
            raise ValueError(
                f"lam is the sparse-structure glasso penalty; got "
                f"lam={self.lam!r} with structure='tree' (did you mean "
                f"structure='sparse'?)")
        if self.wire not in _WIRES:
            raise ValueError(f"unknown wire {self.wire!r}")
        if self.placement not in _PLACEMENTS:
            raise ValueError(f"unknown placement {self.placement!r}")
        if self.mst not in _MSTS:
            raise ValueError(f"unknown mst backend {self.mst!r}")
        if self.method == "sign":
            object.__setattr__(self, "rate", 1)
        elif self.method == "original":
            # unquantized baseline: raw f32 samples are the wire
            object.__setattr__(self, "wire", "float32")
            object.__setattr__(self, "rate", 32)
        else:
            if not 1 <= self.rate <= 7:
                raise ValueError(
                    f"persymbol rate must be in [1, 7], got {self.rate}")
            if self.wire == "packed" and 8 % self.rate != 0:
                raise ValueError(
                    f"packed wire needs rate | 8, got {self.rate}")
        if self.method != "original" and self.wire == "float32":
            raise ValueError("float32 wire is the unquantized baseline; "
                             "use method='original'")
        if not isinstance(self.channel, Channel):
            raise TypeError(
                f"channel must be a repro.comm.channel.Channel, got "
                f"{type(self.channel)!r}")
        # the channel vetoes (method, wire, placement) combinations it
        # cannot carry — AFTER the normalizations above, so it sees the
        # final values
        self.channel.validate(self)

    @property
    def label(self) -> str:
        """Legend name used across the paper figures and result tables.

        Sparse strategies carry the glasso penalty in the label (e.g.
        ``"R4+glasso0.06"``), so a hand-rolled lambda sweep — S copies of
        one strategy differing only in ``lam`` — keys distinct result
        columns. That per-label path pattern is DEPRECATED (it re-solves
        ISTA cold for every penalty): declare the grid once with
        ``TrialPlan(path=PathPlan(...))`` and the fused warm-started path
        engine solves it in one launch with on-device model selection
        (full-grid curves land in ``TrialResult.path``). Per-lam labels
        keep working for fixed-penalty plans.
        """
        if self.method == "sign":
            base = "sign"
        elif self.method == "original":
            base = "original"
        else:
            base = f"R{self.rate}"
        if self.structure == "sparse":
            base = f"{base}+glasso{self.lam:g}"
        # channel suffix ('' for gather — pre-channel labels unchanged;
        # '@mac{M}' / '@bgt{B}' key distinct result columns per channel)
        return base + self.channel.suffix

    @property
    def bits_per_symbol(self) -> int:
        """ACTUAL wire cost per transmitted symbol for this wire format.

        Equals the paper's R bits/symbol (§3) only on the dense 'packed'
        wire; the 'int8' wire spends a full byte per code and 'float32'
        a full float. Use ``rate`` for the paper's idealized budget.
        """
        if self.wire == "packed":
            return self.rate
        return 32 if self.wire == "float32" else 8

    def logical_bits(self, n: int, d: int) -> int:
        """The paper's idealized communication budget: n * d * R bits (§3)
        — R information bits per transmitted symbol, independent of how
        the wire actually frames them. Pair with :meth:`wire_bits` for the
        honest cost (the two agree only on the dense 'packed' wire)."""
        return n * d * self.rate

    def wire_bits(self, n: int, d: int) -> int:
        """Bits an (n, d) dataset ACTUALLY moves under this strategy's wire
        format: n * d * bits_per_symbol. A 'float32' wire spends 32
        bits/symbol and an 'int8' wire 8 bits/symbol REGARDLESS of R —
        only the dense 'packed' wire achieves the paper's n * d * R
        (:meth:`logical_bits`)."""
        return n * d * self.bits_per_symbol

    def communication_bits(self, n: int, d: int) -> int:
        """Alias of :meth:`wire_bits` (the honest accounting), kept for
        callers of the original name; use :meth:`logical_bits` for the
        paper's idealized n * d * R."""
        return self.wire_bits(n, d)

    def packed_gram_ok(self, n: int) -> bool:
        """True when the dense packed payload of ``n`` samples can feed the
        Gram engine directly (no unpack in HBM): sign method,
        packed wire, and n a multiple of the 8-symbol byte granularity.
        The estimators fall back to the (statistically identical) int8
        contraction otherwise — shape buckets are powers of two precisely
        so bucketed sweeps never lose this path."""
        return self.method == "sign" and self.wire == "packed" and n % 8 == 0


def as_strategy(strategy: Strategy | None, **kw) -> Strategy:
    """Normalize the (strategy | loose kwargs) calling conventions.

    ``strategy`` wins when given; otherwise a Strategy is built from the
    legacy kwargs (unknown keys rejected by the dataclass constructor).
    """
    if strategy is not None:
        if kw:
            strategy = dataclasses.replace(strategy, **kw)
        return strategy
    return Strategy(**kw)


#: The six-curve suite of Fig. 3 — the paper's headline comparison.
FIG3_STRATEGIES: tuple[Strategy, ...] = (
    Strategy("sign"),
    Strategy("persymbol", rate=1),
    Strategy("persymbol", rate=2),
    Strategy("persymbol", rate=3),
    Strategy("persymbol", rate=4),
    Strategy("original"),
)
