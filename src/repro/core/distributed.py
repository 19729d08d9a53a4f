"""Distributed structure learning over a device mesh (hardware adaptation).

The paper's topology — d leaf machines each holding one feature, a central
machine running Chow-Liu — maps onto a TPU mesh as a *vertical model*
sharding problem:

  * features (dimensions) are sharded over the ``model`` mesh axis
    (each device plays a block of the paper's machines M_j),
  * samples are sharded over the ``data`` mesh axis,
  * "transmit R-bit codes to the center" becomes: quantize locally, then
    **all-gather the integer codes over the model axis**. The all-gather
    payload is exactly the paper's communication cost (ndR bits, eq. in §3),
  * the central machine's pairwise-statistic computation becomes a Gram
    contraction each device performs on its sample shard, followed by a
    **psum over the data axis**; the MWST then runs on the replicated
    weight matrix (device-side Boruvka) or on the host (Kruskal).

The runtime is decomposed into three individually jit/vmap-able stages,
carried by :class:`WirePlan` (the executable companion of the declarative
:class:`~repro.core.strategy.Strategy`):

  * :meth:`WirePlan.encode`  — per-machine local quantization: the rank's
    feature slice -> its wire payload (``estimators.strategy_payload``);
  * :meth:`WirePlan.wire`    — THE communication the paper counts: one
    tiled all-gather of the payload over the model axis. Static payload
    shapes make the cost exactly accountable — :meth:`WirePlan.comm_report`
    measures it with ``jax.eval_shape`` on the encode stage and returns a
    :class:`CommReport` (logical n*d*R bits vs bytes actually gathered);
  * :meth:`WirePlan.central` — the center: Gram contraction on the
    gathered payload (``estimators.payload_gram``, placement-aware) +
    Chow-Liu weights (``estimators.weights_from_gram`` — the same math
    every other pipeline runs; nothing is duplicated here).

:func:`build_weights_fn` shard_maps the composed
``encode -> wire -> central`` chain (:meth:`WirePlan.local_weights`) for
one dataset and jits it, once per (mesh, strategy, axes, engine, glasso
steps, path): a later call with the same mesh, strategy and shapes
traces, lowers and compiles nothing. ``experiments.clear_compile_caches``
drops these runtimes with the trial plane's stages.
``experiments.run_trials(plan, mesh=("data","model"))`` runs
the SAME stages over the Monte-Carlo trial plane — trials sharded over
``data``, features over ``model`` — with per-strategy ``CommReport``
telemetry and bit-identical metrics to the single-device engine.

Every Gram goes through :class:`repro.core.gram.GramEngine` (Pallas kernels
on TPU, XLA matmuls on CPU). For ``wire="packed"`` with the sign method
the Gram is computed **directly on the packed payload**
(G = n - 2*popcount(xor)) — the gathered wire bytes are the kernel operand,
nothing is unpacked back to int8/f32. For int8 wires, codes enter the kernel
as int8 (sign upcast / centroid decode fused per tile).

Two compute placements are provided (see EXPERIMENTS.md §Perf):
  * ``replicated``: every device computes the full (d, d) Gram of its sample
    shard — redundant over the model axis but collective-minimal (one
    all-gather + one psum). This is the paper-faithful baseline: compute is
    cheap, links are the bottleneck the paper optimizes.
  * ``rowblock``: each model-rank computes only its (d/M, d) row block, and
    row blocks are all-gathered at the end — less compute, one extra
    collective; wins when d is large enough that the Gram dominates.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.spans import span, spanned
from . import estimators, glasso
from .chow_liu import adjacency_to_edges, boruvka_mst, kruskal_mst
from .glasso import DEFAULT_STEPS as GLASSO_STEPS
from .gram import GramEngine
from .path import PathPlan, glasso_path_select
from .strategy import Strategy


def communication_bits(n: int, d: int, rate: int) -> int:
    """The paper's LOGICAL communication cost: n*d*R bits (§3).

    This is the idealized budget (R information bits per symbol); what a
    given wire format actually moves is ``Strategy.wire_bits(n, d)`` —
    32 bits/symbol on a float32 wire and 8 on an int8 wire regardless of
    R. The two agree only on the dense 'packed' wire.
    """
    return n * d * rate


@dataclasses.dataclass(frozen=True)
class CommReport:
    """Honest communication accounting for one weights evaluation.

    Attributes:
      logical_bits: the paper's idealized n*d*R budget (§3) for the true
        sample count n.
      wire_bytes: bytes the model-axis all-gather ACTUALLY assembles at
        the center — measured from the encode stage's static payload
        shapes (so shape-bucket padding, int8 framing and float32 wires
        all show up), not recomputed from a formula.
      collectives: collectives one weights evaluation issues in the wire
        runtime (payload all-gather, + the rowblock row gather; the
        classic data-sharded runtime adds its Gram psum).
      retry_bytes: MEAN bytes per trial re-sent by the fault plane's
        bounded retry policy (``FaultPlan.retries``) — MEASURED from the
        realized per-round retransmission counts of the sweep's fault
        telemetry (machines re-requested x their exact per-machine payload
        bytes), never estimated from the dropout probability. 0.0 without
        a retry policy.
      retry_collectives: mean EXTRA gather rounds per trial that carried
        at least one retransmission (measured the same way). The total
        collective count of a faulty evaluation is
        ``collectives + retry_collectives``.
      retry_rounds: the configured retry budget (``FaultPlan.retries``);
        0 = single-round wire, faults or not.
      rates: per-machine bit-rate ledger ((machines,) ints) for channels
        that differentiate machines — a ``BudgetChannel``'s allocation,
        or the MAC wire's uniform 1-bit signalling. ``None`` on the plain
        gather wire (every machine sends at ``strategy.rate``; the
        pre-channel reports are field-for-field unchanged).
      machine_bits: per-machine wire-bit ledger ((machines,) ints) —
        the bits machine m actually put on the channel (its delivered
        symbols x its rate). ``sum(machine_bits) == logical_bits`` for
        the budget channel (and <= its ``budget_bits`` by construction).
        ``None`` on the plain gather wire.
    """

    logical_bits: int
    wire_bytes: int
    collectives: int
    retry_bytes: float = 0.0
    retry_collectives: float = 0.0
    retry_rounds: int = 0
    rates: tuple[int, ...] | None = None
    machine_bits: tuple[int, ...] | None = None

    @property
    def wire_bits(self) -> int:
        return 8 * self.wire_bytes

    @property
    def retry_bits(self) -> float:
        """Measured mean retransmitted bits per trial (8 * retry_bytes) —
        the third column of the logical / wire / retry accounting."""
        return 8.0 * self.retry_bytes

    @property
    def overhead(self) -> float:
        """wire bits / logical bits — 1.0 means the wire is as dense as
        the paper's budget (packed, no padding). Retry bits are excluded
        (they are a fault-recovery cost, not a framing cost)."""
        return 8.0 * self.wire_bytes / max(self.logical_bits, 1)


def _as_wire_strategy(
    strategy: Strategy | None, method: str, rate: int, compute: str, wire: str
) -> Strategy:
    """Normalize (strategy | loose kwargs) to the runtime's Strategy.

    The loose spelling ``wire='float32'`` (raw samples gathered, eq.-1
    weights) is the unquantized baseline: ``method='original'``.
    """
    if strategy is not None:
        return strategy
    if wire == "float32":
        return Strategy("original", placement=compute)
    return Strategy(method, rate=rate, wire=wire, placement=compute)


@dataclasses.dataclass(frozen=True)
class WirePlan:
    """Stage-decomposed wire runtime for one Strategy on a device mesh.

    Frozen + hashable (usable as a jit-cache key next to Strategy). The
    three stages are pure functions of their operands — individually
    jit/vmap-able, composable inside any ``shard_map`` whose mesh carries
    ``model_axis`` (and ``data_axis`` for the sample-sharded runtime):

      ``encode``  (per machine)  ->  ``wire``  (THE collective)  ->
      ``central`` (Gram + weights at the center).

    Payloads may carry a leading batch axis (the trial plane's trial
    dimension); every stage passes it through to the engine's batched
    kernels.
    """

    strategy: Strategy
    data_axis: str = "data"
    model_axis: str = "model"
    engine: GramEngine | None = None
    #: ISTA iteration budget of the central glasso solve (sparse
    #: structures only; tree strategies never read it)
    glasso_steps: int = GLASSO_STEPS
    #: optional regularization-path plan (``core.path.PathPlan``, sparse
    #: strategies only): :meth:`central` solves the warm-started lambda
    #: grid in one fused launch after the gather and returns the
    #: MODEL-SELECTED precision matrix (EBIC per trial; StARS treats a
    #: leading batch axis as the subsample batch) instead of solving the
    #: strategy's fixed ``lam``. ``None`` = the fixed-penalty solve.
    path: PathPlan | None = None

    # ---- stage 1: local encoding, R bits/symbol (paper step 1) ----------

    def encode(self, x_loc: jax.Array, *,
               n_valid: jax.Array | int | None = None,
               n_rows: jax.Array | None = None,
               flip: jax.Array | None = None,
               rates: jax.Array | None = None) -> jax.Array:
        """Per-machine quantization of the rank's (..., n, d_loc) feature
        slice into its wire payload (``estimators.strategy_payload``
        layouts). ``n_valid`` threads the trial plane's valid-length mask;
        ``n_rows`` / ``flip`` thread this rank's FEATURE-SLICE of a fault
        plan's realization (delivered-row counts and sign bit-flips — see
        ``core.faults``), applied machine-side exactly as the estimator
        stage chain applies them.

        ``rates`` is how the encode consults the channel for this rank's
        transmit rate: under a :class:`~repro.comm.channel.BudgetChannel`
        it is the (d_loc,) slice of the channel's per-feature rate
        allocation, and the payload becomes the mixed-rate codes of
        ``estimators.budget_payload`` (rate-0 features stay silent as
        ``MASKED_CODE``). Gather/MAC strategies must not pass it — their
        rate is the strategy's own, uniform.
        """
        s = self.strategy
        if s.channel.kind == "budget":
            assert rates is not None, \
                "budget-channel encode needs this rank's rates slice"
            return estimators.budget_payload(x_loc, s, rates,
                                             n_valid=n_valid, n_rows=n_rows)
        assert rates is None, "rates= is the budget channel's operand"
        if s.wire == "packed":
            per = 8 // s.rate
            assert x_loc.shape[-2] % per == 0, (
                f"packed wire needs the sample count to be a multiple of "
                f"{per} (got {x_loc.shape[-2]}); bucket n (pow2 buckets "
                f"always qualify) or use the int8 wire")
        payload = estimators.strategy_payload(x_loc, s, n_valid=n_valid,
                                              n_rows=n_rows, flip=flip)
        if s.wire == "packed":
            assert payload.dtype == jnp.uint8, "packed wire must stay packed"
        return payload

    # ---- stage 2: transmit to center == all-gather over model (step 2) --

    def feature_axis(self, payload: jax.Array) -> int:
        """Index of the feature axis in a payload (packed wires are
        feature-major, everything else sample-major)."""
        return payload.ndim - (2 if payload.dtype == jnp.uint8 else 1)

    def wire(self, payload: jax.Array,
             keep: jax.Array | None = None) -> jax.Array:
        """THE communication the paper counts — dispatched to the
        strategy's channel (``strategy.channel.transmit``): a tiled
        all-gather of the payload over the model axis for gather/budget
        channels (reassembling the full feature dimension in rank order,
        bit-identical to encoding the unsliced data — the trial-plane
        parity gate), the superposing psum for the MAC channel (the
        payload is then this rank's PARTIAL statistic, and the center
        receives only the sum).

        ``keep`` — optional (d_loc,) bool per-feature survival flags (a
        fault plan's ``n_rows > 0``): the gather still runs (SPMD), but a
        dropped machine's entries arrive at the center as the format's
        masked value (``comm.collectives.erasure_all_gather``, with the
        fill sentinel from the channel layer's single
        ``comm.collectives.neutral_fill``) — the channel itself erases
        the lost payload. Bit-identical to the encode-stage masking, so
        either realization satisfies the parity gate.
        """
        from repro.comm.collectives import neutral_fill

        return self.strategy.channel.transmit(
            payload, self.model_axis, axis=self.feature_axis(payload),
            keep=keep,
            fill=neutral_fill(self.strategy.method, payload.dtype))

    # ---- stage 3: central statistic + weights (paper step 3) ------------

    def central(
        self,
        payload_full: jax.Array,
        n,
        *,
        n_valid: jax.Array | int | None = None,
        n_rows: jax.Array | None = None,
        n_rows_own: jax.Array | None = None,
        own_payload: jax.Array | None = None,
        data_sharded: bool = False,
    ) -> jax.Array:
        """The center: Gram contraction on the gathered payload + the
        central estimate, via the SAME ``estimators`` stage functions every
        other pipeline runs.

        For ``structure='tree'`` strategies the estimate is the Chow-Liu
        weight matrix (``estimators.weights_from_gram``); for
        ``structure='sparse'`` it is the sparse precision matrix — the
        correlation statistic (``estimators.corr_from_gram``, arcsine
        inversion + PSD repair for the sign method) fed through the
        batched device glasso (one fused solve for a whole trial batch).

        Args:
          payload_full: the gathered (full-feature) payload.
          n: total sample count for the weight normalization (python int,
            or traced f32 under valid-length masking). Ignored when
            ``n_rows`` is given — the fault plane normalizes by the
            per-entry effective pairwise counts instead.
          n_rows: the fault plan's (..., d) FULL-feature delivered-row
            counts (every rank reconstructs them deterministically from
            the replicated fault keys): selects the masked-Gram path and
            the ``estimators.effective_counts`` normalization.
          n_rows_own: this rank's feature-slice of ``n_rows`` (rowblock
            placement only — masks the pre-gather row operand).
          own_payload: this rank's pre-gather payload — the lhs row block
            under the ``rowblock`` placement (its features ARE the rank's
            rows of the full payload, no slicing needed).
          data_sharded: samples are sharded over ``data_axis`` (the
            classic runtime): psum the Gram over it before the weights.
        """
        s = self.strategy
        gram = self._assemble_gram(payload_full, n_valid=n_valid,
                                   n_rows=n_rows, n_rows_own=n_rows_own,
                                   own_payload=own_payload,
                                   data_sharded=data_sharded)
        if n_rows is not None:
            n = estimators.effective_counts(n_rows)
        if s.structure == "sparse":
            corr = estimators.corr_from_gram(gram, n, s)
            if self.path is not None:
                # path mode: one fused warm-started grid scan + on-device
                # selection — the center returns the SELECTED precision.
                # EBIC's likelihood scale is the sample count; under the
                # fault plane's per-entry effective counts, its mean is
                # the honest scalar stand-in.
                n_eff = jnp.mean(jnp.asarray(n, jnp.float32))
                theta, _, _ = glasso_path_select(
                    corr, self.path, n_eff, n_steps=self.glasso_steps)
                return theta
            solve = glasso.glasso_batch if corr.ndim == 3 else glasso.glasso
            return solve(corr, s.lam, n_steps=self.glasso_steps)
        return estimators.weights_from_gram(gram, n, s)

    def _assemble_gram(
        self,
        payload_full: jax.Array,
        *,
        n_valid: jax.Array | int | None = None,
        n_rows: jax.Array | None = None,
        n_rows_own: jax.Array | None = None,
        own_payload: jax.Array | None = None,
        data_sharded: bool = False,
    ) -> jax.Array:
        """The center's full (d, d) Gram from the gathered payload:
        placement-aware contraction (+ the rowblock row gather / the
        data-axis psum). The one copy both :meth:`central` and
        :meth:`central_corr` build on. ``n_rows`` / ``n_rows_own`` select
        the fault plane's per-feature masked contraction (under rowblock,
        different machines' dropouts void different row blocks of the
        gathered Gram — each block stays honestly masked)."""
        s = self.strategy
        rows = own_payload if s.placement == "rowblock" else None
        gram = estimators.payload_gram(
            payload_full, s, n_valid=n_valid, n_rows=n_rows,
            payload_rows=rows,
            n_rows_rows=n_rows_own if rows is not None else None,
            engine=self.engine)
        if data_sharded:
            gram = jax.lax.psum(gram, self.data_axis)
        if s.placement == "rowblock":
            # the tiled all_gather replicates the row blocks; under the
            # replicated placement every model rank already holds the
            # whole Gram
            gram = jax.lax.all_gather(
                gram, self.model_axis, axis=gram.ndim - 2, tiled=True)
        return gram

    def central_corr(
        self,
        payload_full: jax.Array,
        n,
        *,
        n_valid: jax.Array | int | None = None,
        n_rows: jax.Array | None = None,
        n_rows_own: jax.Array | None = None,
        own_payload: jax.Array | None = None,
        data_sharded: bool = False,
    ) -> jax.Array:
        """The center's PRE-SOLVE statistic for a sparse strategy: Gram on
        the gathered payload + ``estimators.corr_from_gram`` (arcsine
        inversion and PSD repair for the sign method), WITHOUT the glasso
        solve.

        The sparse trial plane ends its shard_map here: the correlation
        statistic is bit-stable across shardings (integer-exact sign
        Grams, batch-stable eigh), while the ISTA loop's fused reductions
        are compilation-context-sensitive — so ``run_trials`` gathers
        these statistics and runs the solve+metric stage through the SAME
        single-device executable as the mesh-less engine, which is what
        makes the sparse parity gate bit-exact. The path-mode wire
        runtime (:meth:`local_corr`) ends here too, for the same reason
        — plus the path engine's masked ``while_loop`` has no shard_map
        replication rule, so the fused grid scan must run outside.
        """
        s = self.strategy
        assert s.structure == "sparse", "central_corr is the sparse center"
        gram = self._assemble_gram(payload_full, n_valid=n_valid,
                                   n_rows=n_rows, n_rows_own=n_rows_own,
                                   own_payload=own_payload,
                                   data_sharded=data_sharded)
        if n_rows is not None:
            n = estimators.effective_counts(n_rows)
        return estimators.corr_from_gram(gram, n, s)

    def central_from_sum(self, gram_sum: jax.Array, n_eff,
                         *, corr: bool = False) -> jax.Array:
        """The MAC center: the channel delivered the SUPERPOSED sum
        statistic (``comm.collectives.superposed_psum`` of every
        machine's partial sign Gram) — per-machine payloads never existed
        at the center, so the estimate is a function of the sum and the
        effective sample count alone (``estimators.mac_estimate``; a
        dropped machine is a missing summand already absent from both).
        The sum-statistic twin of :meth:`central` / :meth:`central_corr`.
        """
        assert self.strategy.channel.kind == "mac", \
            "central_from_sum is the MAC channel's center"
        return estimators.mac_estimate(gram_sum, self.strategy, n_eff,
                                       corr=corr)

    # ---- composed runtime + accounting ----------------------------------

    def local_weights(self, x_loc: jax.Array) -> jax.Array:
        """The classic sample+feature sharded runtime body: one device's
        (n_loc, d_loc) block -> the replicated (d, d) weights. This is the
        function :func:`build_weights_fn` shard_maps."""
        n = x_loc.shape[0] * jax.lax.axis_size(self.data_axis)
        payload = self.encode(x_loc)
        full = self.wire(payload)
        return self.central(full, n, own_payload=payload, data_sharded=True)

    def local_corr(self, x_loc: jax.Array) -> jax.Array:
        """The path-mode shard_map body: the same stage chain as
        :meth:`local_weights` but ending at the replicated correlation
        statistic (:meth:`central_corr`). :func:`build_weights_fn` runs
        the warm-started path solve OUTSIDE the shard_map on this output
        — the statistic is bit-stable across shardings, so the selected
        structure is automatically mesh-parity-exact."""
        n = x_loc.shape[0] * jax.lax.axis_size(self.data_axis)
        payload = self.encode(x_loc)
        full = self.wire(payload)
        return self.central_corr(full, n, own_payload=payload,
                                 data_sharded=True)

    def comm_report(self, n: int, d: int, *,
                    n_pad: int | None = None) -> CommReport:
        """Measured communication accounting for one (n, d) evaluation.

        ``wire_bytes`` comes from ``jax.eval_shape`` on the encode stage
        at the shape the sweep actually gathers (``n_pad`` under shape
        bucketing — padding costs real bytes and is reported as such);
        ``logical_bits`` uses the true n (the paper's §3 budget).

        Channel-aware: the gather wire reports exactly the pre-channel
        numbers (field for field — the PR-4 accounting pins); the MAC
        wire's received payload is the (d, d) f32 superposed statistic
        (per-machine signals never traverse a link individually — their
        1-bit airtime is the ``machine_bits`` ledger); the budget wire
        reports its measured int8 code gather plus the per-machine
        rate/bit ledgers of its allocation (``sum(machine_bits) ==
        logical_bits <= budget_bits``).
        """
        n_wire = n if n_pad is None else n_pad
        s = self.strategy
        ch = s.channel
        if ch.kind == "mac":
            stat = jax.eval_shape(
                lambda g: g, jax.ShapeDtypeStruct((d, d), jnp.float32))
            b = ch.block_rows(n_wire)
            delivered = [max(0, min(n - m * b, b))
                         for m in range(ch.machines)]
            return CommReport(
                logical_bits=communication_bits(n, d, s.rate),
                wire_bytes=int(np.prod(stat.shape)) * stat.dtype.itemsize,
                collectives=1,
                rates=(1,) * ch.machines,
                machine_bits=tuple(r * d for r in delivered))
        if ch.kind == "budget":
            rates_m = ch.allocate(n, d, s.rate)
            d_m = d // ch.machines
            machine_bits = tuple(n * d_m * r for r in rates_m)
            payload = jax.eval_shape(
                lambda x: estimators.budget_payload(
                    x, s, jnp.zeros((d,), jnp.int32)),
                jax.ShapeDtypeStruct((n_wire, d), jnp.float32))
            return CommReport(
                logical_bits=sum(machine_bits),
                wire_bytes=int(np.prod(payload.shape))
                * payload.dtype.itemsize,
                collectives=1, rates=rates_m, machine_bits=machine_bits)
        payload = jax.eval_shape(
            lambda x: estimators.strategy_payload(x, self.strategy),
            jax.ShapeDtypeStruct((n_wire, d), jnp.float32))
        wire_bytes = int(np.prod(payload.shape)) * payload.dtype.itemsize
        collectives = 1 + (1 if self.strategy.placement == "rowblock" else 0)
        return CommReport(
            logical_bits=communication_bits(n, d, self.strategy.rate),
            wire_bytes=wire_bytes, collectives=collectives)


def build_weights_fn(
    mesh: Mesh,
    *,
    strategy: Strategy | None = None,
    method: Literal["sign", "persymbol"] = "sign",
    rate: int = 1,
    data_axis: str = "data",
    model_axis: str = "model",
    compute: Literal["replicated", "rowblock"] = "replicated",
    wire: Literal["int8", "packed", "float32"] = "int8",
    engine: GramEngine | None = None,
    glasso_steps: int = GLASSO_STEPS,
    path: PathPlan | None = None,
):
    """shard_map pipeline (n, d) samples -> (d, d) central estimate
    (Chow-Liu weights, or the glasso precision for a sparse strategy —
    ``glasso_steps`` sets that solve's ISTA budget; ``path`` swaps the
    fixed-penalty solve for the warm-started regularization-path engine
    with on-device EBIC selection, returning the selected precision).

    ``strategy`` (a :class:`~repro.core.strategy.Strategy`) is the
    declarative form of the loose ``method``/``rate``/``compute``/``wire``
    kwargs and wins over them when given; either way the body is the
    :class:`WirePlan` stage chain ``encode -> wire -> central``.

    Wire formats for the model-axis all-gather (THE communication the
    paper counts):
      * 'int8'    — one byte per symbol (±1 signs or bin codes, any
        R <= 7): the easy baseline, already 4-8x under float.
      * 'packed'  — dense R bits/symbol via ``quantizers.pack_codes`` —
        the paper's actual budget (sign = 1 bit/symbol on the wire). For
        the sign method the Gram is contracted directly on this payload.
      * 'float32' — unquantized samples (the centralized-equivalent
        baseline the paper compares against).

    Compute placements: 'replicated' Gram on every rank (collective-
    minimal) vs 'rowblock' (each model rank computes its (d/M, d) rows —
    M-fold fewer FLOPs, one extra (small) all-gather).

    engine: GramEngine the Gram contractions dispatch through (must be a
    traced backend — 'pallas' or 'xla' — inside shard_map; None = process
    default, which auto-selects per platform).

    Returns ``(runtime, sharding)``: the jitted shard_map, which takes
    (n, d) samples placed with ``sharding`` (``P(data_axis,
    model_axis)``). Both are built once per (mesh, strategy, axes, engine,
    ``glasso_steps``, ``path``) and the same objects are returned after.
    """
    strat = _as_wire_strategy(strategy, method, rate, compute, wire)
    if strat.channel.kind != "gather":
        raise ValueError(
            "build_weights_fn is the gather-wire runtime; MAC/budget "
            "channel strategies run through experiments.run_trials (the "
            "trial plane threads their rate/delivered operands)")
    if path is not None and strat.structure != "sparse":
        raise ValueError(
            "path= is the sparse plane's regularization-path engine; "
            "tree strategies have no penalty to select")
    return _wire_runtime(mesh, strat, data_axis, model_axis, engine,
                         glasso_steps, path)


@functools.lru_cache(maxsize=None)
def _wire_runtime(mesh: Mesh, strat: Strategy, data_axis: str,
                  model_axis: str, engine: GramEngine | None,
                  glasso_steps: int, path: PathPlan | None):
    """The jitted runtime of :func:`build_weights_fn` and its input
    sharding, built once per key (``experiments.clear_compile_caches``
    drops them). A miss records one ``repro.wire.build`` span."""
    with span("wire.build"):
        plan = WirePlan(strat, data_axis=data_axis, model_axis=model_axis,
                        engine=engine, glasso_steps=glasso_steps, path=path)
        in_spec = P(data_axis, model_axis)
        # check_vma=False, as on the trial plane's shard_maps: a Pallas
        # kernel's output declares no varying mesh axes, which the check
        # requires, and VMA inference cannot prove an all_gather's output
        # replicated. The out spec is still honest: every rank holds the
        # whole gathered payload, so the whole Gram (or, under rowblock,
        # all its gathered row blocks).
        inner = jax.shard_map(
            plan.local_corr if path is not None else plan.local_weights,
            mesh=mesh,
            in_specs=(in_spec,),
            out_specs=P(),
            check_vma=False,
        )
        if path is None:
            return jax.jit(inner), NamedSharding(mesh, in_spec)

        # the path engine's masked while_loop has no shard_map replication
        # rule; the shard_map ends at the (replicated, sharding-bit-stable)
        # correlation statistic and the fused grid scan + EBIC selection
        # run on top — selected structure is mesh-parity-exact for free.
        def fused_path(x):
            corr = inner(x)
            theta, _, _ = glasso_path_select(
                corr, path, jnp.asarray(x.shape[0], jnp.float32),
                n_steps=glasso_steps)
            return theta

        return jax.jit(fused_path), NamedSharding(mesh, in_spec)


def distributed_weights(
    x: jax.Array,
    mesh: Mesh,
    *,
    strategy: Strategy | None = None,
    method: Literal["sign", "persymbol"] = "sign",
    rate: int = 1,
    data_axis: str = "data",
    model_axis: str = "model",
    compute: Literal["replicated", "rowblock"] = "replicated",
    wire: Literal["int8", "packed", "float32"] = "int8",
    engine: GramEngine | None = None,
    glasso_steps: int = GLASSO_STEPS,
    path: PathPlan | None = None,
) -> jax.Array:
    """Central estimate from vertically-sharded data: the Chow-Liu weight
    matrix, or the glasso precision matrix for a sparse strategy (the
    path-selected one under ``path=``).

    The runtime comes from :func:`build_weights_fn`, built on the first
    call for a mesh and strategy and reused after: a repeat call only
    places ``x`` (one ``repro.wire.place`` span, free when ``x`` is
    already placed so) and dispatches the runtime (one
    ``repro.wire.weights`` span).

    Args:
      x: (n, d) samples; will be placed as P(data_axis, model_axis) — each
        device holds a (n/D, d/M) block, i.e. the paper's vertical partition.
      strategy: declarative Strategy (wins over the loose kwargs).
    Returns:
      (d, d) estimate, fully replicated.
    """
    fn, sharding = build_weights_fn(
        mesh, strategy=strategy, method=method, rate=rate,
        data_axis=data_axis, model_axis=model_axis, compute=compute,
        wire=wire, engine=engine, glasso_steps=glasso_steps, path=path)
    with span("wire.place"):
        x = jax.device_put(x, sharding)
    with span("wire.weights"):
        return fn(x)


@spanned("distributed_learn_structure")
def distributed_learn_structure(
    x: jax.Array,
    mesh: Mesh,
    *,
    strategy: Strategy | None = None,
    method: Literal["sign", "persymbol"] = "sign",
    rate: int = 1,
    backend: str | None = None,
    **kw,
) -> list[tuple[int, int]]:
    """End-to-end distributed structure learning: the estimated edges.

    Tree strategies return the Chow-Liu MWST edges; sparse strategies
    (``strategy.structure == 'sparse'``) return the glasso support edges
    (``glasso.support`` with ``kw['tol']`` if given — the central estimate
    from the wire runtime is the precision matrix itself). Passing
    ``path=PathPlan(...)`` in ``kw`` routes the central solve through the
    warm-started regularization-path engine, so the returned edges are
    the EBIC-SELECTED structure — no caller-chosen penalty needed.

    The MWST solver comes from ``backend`` if given, else
    ``strategy.mst``, else the on-device Boruvka default, which hands the
    host its O(d log d) edge record (``boruvka_mst(w, edges=True)``), not
    the (d, d) adjacency.

    Each call is one ``repro.distributed_learn_structure`` span; a tree
    strategy's MWST solve (its dispatch, for Boruvka) is one
    ``repro.structure.mst`` span inside it, as in ``learn_structure``.
    """
    if strategy is not None and strategy.structure == "sparse":
        from .glasso import SUPPORT_TOL, support

        if backend is not None:
            raise ValueError(
                "backend= names an MWST solver; sparse strategies recover "
                "a glasso support (tune tol= instead)")
        tol = kw.pop("tol", SUPPORT_TOL)
        w = distributed_weights(x, mesh, strategy=strategy, method=method,
                                rate=rate, **kw)
        return adjacency_to_edges(support(w, tol))
    # tree strategies: kw passes through verbatim (an unknown kwarg like
    # tol= still fails loudly instead of being silently swallowed)
    w = distributed_weights(x, mesh, strategy=strategy, method=method,
                            rate=rate, **kw)
    if backend is None:
        backend = strategy.mst if strategy is not None else "boruvka"
    if backend == "boruvka":
        # device solve on the replicated weights; only its edge record
        # crosses to the host, at the edge-list surface
        with span("structure.mst"):
            record = boruvka_mst(w, edges=True)
        return adjacency_to_edges(record)
    with span("structure.mst"):
        return kruskal_mst(np.asarray(w))
