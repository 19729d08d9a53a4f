"""One-launch sweep engine: bucketed, batched, shardable Monte-Carlo sweeps.

The paper's results are all Monte-Carlo estimates — Pr(T_hat != T) over
hundreds of (tree, data, method, R, n) trials (Figs. 3-11). The reference
loop (``recovery_error_rate`` in ``tests/test_experiments.py``) executes
one trial at a time through Python with a host numpy round-trip per
trial. This module replaces it with a batched engine built from three
stacked optimizations:

* **Shape bucketing** — each sample size n is padded up to a small set of
  buckets (powers of two by default; ``TrialPlan.n_buckets`` overrides)
  and an explicit valid-length mask is threaded through
  sampler -> quantizer -> Gram -> weights, so the weights stage compiles
  once per (strategy set, bucket) instead of once per (strategy, n). The
  sampler draws per-row PRNG streams (``sampler.sample_tree_ggm_rows``),
  so padded draws are bit-equal to unpadded ones on the valid prefix, and
  the integer-exact sign Grams are bit-equal through the mask — bucketing
  cannot change which tree Boruvka recovers.
* **Batched kernel grids** — the whole trial axis enters the Gram engine
  through its ``*_batch`` entry points (``GramEngine.gram_batch`` /
  ``code_gram_batch`` / ``packed_sign_gram_batch``), which on the pallas
  backend make the trial axis a native leading grid dimension of ONE
  kernel launch. All strategies' weight tensors are stacked per n and the
  MWST + metric stage runs as one (S*reps, d, d) launch, accumulating the
  (S, len(ns), 3) metric tensor on device: a full sweep performs exactly
  ONE ``jax.device_get`` host sync, however many points it has.
* **Mesh sharding** — ``run_trials(..., mesh=...)`` shard_maps the rep
  axis over the mesh's ``"data"`` axis (``launch.mesh.make_trial_mesh``)
  with a psum-reduced metric stage, scaling sweeps across
  ``--xla_force_host_platform_device_count`` CPUs today and real
  accelerator meshes unchanged.
* **Distributed trial plane** — a 2-D ``("data", "model")`` mesh
  (``make_trial_mesh(model=...)``) runs every trial through the
  stage-decomposed wire runtime (``distributed.WirePlan``): trials shard
  over ``data``, features over ``model``, and each trial's encode ->
  all-gather -> central chain issues the paper's ACTUAL collectives. The
  per-trial metric sums are integer-exact (error indicator, edge
  symmetric difference, shared-edge count), so the psum-reduced results
  are bit-identical to the single-device engine, and every strategy's
  wire cost is reported as a :class:`~repro.core.distributed.CommReport`
  (logical n*d*R bits vs bytes actually gathered) on ``TrialResult.comm``.

The MWST inside the trial plane is the device Boruvka solver
(exact-equal to host Kruskal by the shared edge order);
``run_trials(..., mst="host_kruskal")`` is the escape hatch for future
solvers that break that order equivalence — it pulls the weight tensors
back in ONE stacked device_get and runs the host Kruskal + host metrics
loop, metric-identical to the device path on the current estimators.

* **Sparse trial plane** — the paper's §7 extension ("glasso over the
  quantized data") as a first-class scenario: a plan whose strategies
  carry ``structure="sparse"`` (+ a ``lam`` penalty) sweeps random sparse
  precision ground truths (``tree="sparse"``,
  ``glasso.random_sparse_precision``) through the same
  sample -> quantize -> Gram chain, with the central solve swapped from
  Boruvka to the BATCHED device glasso: the whole (S*reps, d, d) point is
  one fused vmapped ISTA launch, support is thresholded on normalized
  partial correlations on device, and the five integer-exact support
  channels (error, Hamming, shared/est/true edge counts) recover
  precision/recall/micro-F1 exactly — still ONE host sync per sweep.
  Under a mesh the corr stage (and the wire plane's actual all-gather)
  shard_maps exactly like the tree plane, but the solve+metric stage runs
  through the shared single-device executable (statistics gathered by a
  device_put, not a host sync), so mesh results are bit-identical to the
  mesh-less engine by construction.

:func:`mc_sign_crossover` / :func:`mc_persymbol_corr_error` are the
analogous vmapped engines for the scalar Monte-Carlo curves of
Figs. 5-6, 8 and 9.

Trees + trial keys (host Pruefer/BFS, O(reps * d), cached per plan) and
the final metric-tensor read-back are the only host work. The module-level
compile caches are inspectable (:func:`compile_cache_size`, surfaced in
``TrialResult`` telemetry) and resettable (:func:`clear_compile_caches`)
so long-lived sweep services can bound their footprint.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.spans import span, spanned
from . import estimators, glasso, sampler, trees
from . import path as path_engine
from .path import PathPlan
from .chow_liu import boruvka_mst_batch, kruskal_mst
from .distributed import CommReport, WirePlan, _wire_runtime
from .faults import FaultPlan, fault_trial_keys
from .gram import (GramConfig, GramEngine, default_memory_budget,
                   gram_working_set_bytes, resolve_engine)
from .quantizers import PerSymbolQuantizer
from .strategy import FIG3_STRATEGIES, Strategy

TREE_KINDS = ("random", "star", "chain", "skeleton")
#: ground-truth generators of the SPARSE trial plane (the §7 extension):
#: random sparse diagonally-dominant precision matrices
#: (``glasso.random_sparse_precision``)
SPARSE_KINDS = ("sparse",)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and >= 8, the packed-wire byte floor)."""
    return max(8, 1 << max(int(n) - 1, 1).bit_length())


def _gram_path(s: Strategy) -> str:
    """Which GramEngine path a strategy's payload contracts through
    (the key of ``gram.gram_working_set_bytes``)."""
    if s.method == "original":
        return "f32"
    if s.method == "sign":
        return "packed" if s.wire == "packed" else "int8"
    return "code"


# --------------------------------------------------------------------------
# Declarative sweep plan + result
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrialPlan:
    """A full Monte-Carlo sweep: reps trials per (strategy, n) point.

    Mirrors the knobs of the reference loop (``GGMDataset`` + per-rep
    seeds): trial ``rep`` draws its tree and edge correlations from
    ``np.random.default_rng(seed0 + rep)`` — topology per ``tree`` kind,
    correlations Uniform[rho_min, rho_max] — and its samples from a PRNG
    key folded per rep (and per sample row, so draws are bucket-stable).

    ``n_buckets`` controls shape bucketing of the compiled weights stage:
      * ``"pow2"`` (default) — pad each n up to the next power of two;
      * an explicit tuple of bucket sizes — each n uses the smallest
        bucket >= n (must cover max(ns); multiples of 8 keep the packed
        sign path);
      * ``None`` — exact shapes, one compile per (strategy set, n): the
        PR-2 behavior, still bit-identical in recovered trees.
    """

    d: int
    ns: tuple[int, ...]
    strategies: tuple[Strategy, ...] = FIG3_STRATEGIES
    reps: int = 30
    tree: str = "random"
    rho_min: float = 0.4
    rho_max: float = 0.9
    seed0: int = 0
    n_buckets: tuple[int, ...] | str | None = "pow2"
    #: edge density of the sparse ground-truth precision (sparse plans
    #: only; ``rho_min``/``rho_max`` double as the |Theta_jk| strength
    #: range of ``glasso.random_sparse_precision``)
    density: float = 0.2
    #: partial-correlation support threshold of the sparse metric stage
    glasso_tol: float = glasso.SUPPORT_TOL
    #: ISTA iteration budget of the batched glasso solve
    glasso_steps: int = glasso.DEFAULT_STEPS
    #: optional fault-injection plan (``core.faults.FaultPlan``):
    #: deterministic machine dropout / straggler truncation / sign
    #: bit-flips on the wire, with masked-Gram graceful degradation at the
    #: center and measured retry accounting on ``TrialResult.comm``.
    #: ``None`` = pristine wire; a ZERO-fault FaultPlan runs the fault
    #: path and is bit-identical to ``None`` (pinned by the CI smoke).
    faults: FaultPlan | None = None
    #: per-device memory budget (bytes) the sweep's transient working sets
    #: must fit: pow2 bucket padding backs off to the minimal 8-multiple,
    #: the Gram engine picks d_tile/n_chunk streaming
    #: (:meth:`budget_engine`), and the MWST/glasso solve stage streams the
    #: (S*reps, d, d) stack in :meth:`metrics_chunk`-sized slabs where the
    #: monolithic forms would exceed it. ``None`` = the backend's reported
    #: HBM limit (``gram.default_memory_budget``). Every budget adaptation
    #: is a deterministic function of the plan, so mesh parity holds.
    memory_budget_bytes: int | None = None
    #: optional regularization-path plan (``core.path.PathPlan``, sparse
    #: plans only): the solve stage becomes ONE warm-started fused grid
    #: scan per sweep point (``path.glasso_path_batch``) with on-device
    #: EBIC/StARS model selection — the headline metrics score the
    #: SELECTED support per trial, the full path's per-lam channels ride
    #: the same single host sync onto ``TrialResult.path``, and the
    #: strategies' per-label ``lam`` values are ignored (the grid comes
    #: from the plan). ``None`` = the fixed-penalty solve stage.
    path: PathPlan | None = None

    def __post_init__(self):
        if self.tree not in TREE_KINDS + SPARSE_KINDS:
            raise ValueError(f"unknown tree kind {self.tree!r}")
        if self.tree == "skeleton" and self.d != 20:
            raise ValueError("skeleton topology is the 20-joint body")
        if self.reps < 1 or self.d < 2:
            raise ValueError("need reps >= 1 and d >= 2")
        object.__setattr__(self, "ns", tuple(int(n) for n in self.ns))
        object.__setattr__(self, "strategies", tuple(self.strategies))
        structures = {s.structure for s in self.strategies}
        if len(structures) > 1:
            raise ValueError(
                "a plan must be homogeneous in Strategy.structure (tree "
                f"and sparse metrics differ), got {sorted(structures)}")
        if (self.tree in SPARSE_KINDS) != (structures == {"sparse"}):
            raise ValueError(
                f"tree kind {self.tree!r} does not match the strategies' "
                f"structure {sorted(structures)}: sparse strategies sweep "
                "over tree='sparse' ground truths and vice versa")
        if self.tree in SPARSE_KINDS and not 0.0 < self.density <= 1.0:
            raise ValueError(f"density must be in (0, 1], got {self.density}")
        nb = self.n_buckets
        if isinstance(nb, str):
            if nb != "pow2":
                raise ValueError(f"unknown bucketing scheme {nb!r}")
        elif nb is not None:
            nb = tuple(sorted(int(b) for b in nb))
            if not nb or nb[0] < 1:
                raise ValueError(f"invalid n_buckets {self.n_buckets!r}")
            if self.ns and max(self.ns) > nb[-1]:
                raise ValueError(
                    f"n_buckets {nb} do not cover max(ns)={max(self.ns)}")
            object.__setattr__(self, "n_buckets", nb)
        if self.faults is not None:
            if not isinstance(self.faults, FaultPlan):
                raise TypeError(
                    f"faults must be a FaultPlan, got {type(self.faults)!r}")
            self.faults.n_machines(self.d)  # machines must divide d
        # each strategy's channel vetoes plan shapes it cannot carry
        # (machine counts vs d, MAC machines vs the fault plan's machines)
        for s in self.strategies:
            s.channel.check_plan(self.d, self.faults)
        if (self.memory_budget_bytes is not None
                and self.memory_budget_bytes <= 0):
            raise ValueError(
                f"memory_budget_bytes must be positive, "
                f"got {self.memory_budget_bytes}")
        if self.path is not None:
            if not isinstance(self.path, PathPlan):
                raise TypeError(
                    f"path must be a PathPlan, got {type(self.path)!r}")
            if self.tree not in SPARSE_KINDS:
                raise ValueError(
                    "path plans ride the sparse plane: TrialPlan(path=...) "
                    "requires tree='sparse' + sparse strategies")

    @property
    def effective_memory_budget(self) -> int:
        """The budget plan decisions run against (bytes): the explicit
        ``memory_budget_bytes`` or the backend default."""
        if self.memory_budget_bytes is not None:
            return self.memory_budget_bytes
        return default_memory_budget()

    def stage_bytes(self, n_pad: int, *, backend: str = "xla",
                    config: GramConfig | None = None) -> int:
        """Analytic peak transient bytes of one weights/corr stage launch
        at bucket ``n_pad``: the shared (reps, n_pad, d) f32 sample block,
        the worst strategy's Gram working set (operands + backend
        transients, ``gram.gram_working_set_bytes``), and the stacked
        (S, reps, d, d) f32 stage output."""
        samples = 4 * self.reps * n_pad * self.d
        gram_ws = max(
            gram_working_set_bytes(
                _gram_path(s), n_pad, self.d, backend=backend,
                config=config, batch=self.reps)
            for s in self.strategies)
        out = 4 * len(self.strategies) * self.reps * self.d * self.d
        return samples + gram_ws + out

    def bucket_for(self, n: int) -> int:
        """The padded sample count the weights stage compiles for.

        Memory-aware: under the ``"pow2"`` scheme, when the stage's
        analytic working set at the pow2 bucket exceeds the plan budget,
        padding backs off to the minimal 8-multiple (the packed-wire byte
        floor) — blind pow2 padding can nearly double the dominant
        (reps, n, d) transients exactly where memory is tightest. Explicit
        bucket tuples and ``None`` are always respected as given.
        """
        if self.n_buckets is None:
            return n
        if self.n_buckets == "pow2":
            b = next_pow2(n)
            floor_b = max(8, -(-n // 8) * 8)
            if (b > floor_b
                    and self.stage_bytes(b) > self.effective_memory_budget):
                return floor_b
            return b
        for b in self.n_buckets:
            if b >= n:
                return b
        raise ValueError(f"no bucket >= {n} in {self.n_buckets}")

    def budget_engine(self, engine: GramEngine) -> GramEngine:
        """Clamp ``engine``'s streaming knobs to the plan's memory budget.

        If the monolithic Gram working set at the largest bucket exceeds
        half the budget (the other half is the stage's sample block and
        output), returns a copy with the largest (d_tile, n_chunk) whose
        tiled working set fits — least streaming that honors the budget.
        Engines with explicit d_tile/n_chunk are returned unchanged. The
        choice depends only on (plan, engine), so every mesh rank and the
        single-device reference agree — the 1-vs-N parity gate is
        budget-safe.
        """
        if (engine.d_tile is not None or engine.n_chunk is not None
                or not self.ns):
            return engine
        backend = engine.resolve()
        budget = self.effective_memory_budget // 2
        n_max = max(self.bucket_for(n) for n in self.ns)
        paths = {_gram_path(s) for s in self.strategies}

        def worst(cfg: GramConfig | None) -> int:
            return max(
                gram_working_set_bytes(p, n_max, self.d, backend=backend,
                                       config=cfg, batch=self.reps)
                for p in paths)

        if worst(engine._config()) <= budget:
            return engine
        for t in (1024, 512, 256, 128):
            if t >= self.d:
                continue
            for nc in (None, 8192, 2048):
                cfg = GramConfig(d_tile=t, n_chunk=nc)
                if worst(cfg) <= budget:
                    return dataclasses.replace(
                        engine, d_tile=t, n_chunk=nc)
        # nothing fits the declared budget: stream as hard as we can
        return dataclasses.replace(
            engine, d_tile=min(128, self.d), n_chunk=1024)

    def metrics_chunk(self) -> int | None:
        """Batch slab size for the MWST/glasso solve stage (``None`` =
        one full vmap over all S*reps trials). The per-trial solver
        transients (~10 (d, d) f32 planes: Boruvka rank/component scratch,
        glasso eigh workspace + carried iterates) must fit half the plan
        budget; where the full stack would not, the stage streams through
        ``lax.map`` in this many trials per slab (bit-identical — trials
        are independent)."""
        trials = len(self.strategies) * self.reps
        per_trial = 40 * self.d * self.d  # ~10 f32 (d, d) planes
        if self.path is not None:
            # a path solve additionally materializes K per-lam (d, d)
            # bool supports per trial on top of the solver transients
            per_trial = (40 + self.path.k) * self.d * self.d
        budget = self.effective_memory_budget // 2
        if trials * per_trial <= budget:
            return None
        return max(1, min(trials, budget // per_trial))

    @property
    def buckets(self) -> dict[int, int]:
        """n -> padded bucket for every sweep point."""
        return {n: self.bucket_for(n) for n in self.ns}

    @property
    def structure(self) -> str:
        """'tree' or 'sparse' — which trial plane the plan runs on
        (homogeneous across strategies by validation)."""
        return "sparse" if self.tree in SPARSE_KINDS else "tree"

    @property
    def points(self) -> int:
        return len(self.ns) * len(self.strategies)

    @property
    def trials(self) -> int:
        return self.points * self.reps


@dataclasses.dataclass
class TrialResult:
    """Per-(strategy, n) Monte-Carlo metrics + engine telemetry."""

    plan: TrialPlan
    #: label -> [Pr(T_hat != T) per n in plan.ns] (sparse plans: Pr of
    #: imperfect support recovery)
    error_rate: dict[str, list[float]]
    #: label -> [mean edge symmetric difference |E_hat ^ E| per n]
    #: (sparse plans: the support Hamming distance)
    edit_distance: dict[str, list[float]]
    #: label -> [edge F1 per n] — spanning trees: mean shared/(d-1);
    #: sparse supports: micro-F1 2*shared/(est+true) recovered exactly
    #: from the integer edge-count channels
    edge_f1: dict[str, list[float]]
    #: wall seconds of the whole ``run_trials`` call, host tree draws
    #: included (the interval of its ``repro.run_trials`` span)
    seconds: float
    #: host syncs the whole sweep performed — exactly 1 (the metric-tensor
    #: device_get); the sweep body never touches the host
    host_syncs: int
    #: label -> [edge precision per n] (micro-averaged shared/est; for
    #: spanning trees est == d-1 so precision == recall == F1)
    precision: dict[str, list[float]] = dataclasses.field(
        default_factory=dict)
    #: label -> [edge recall per n] (micro-averaged shared/true)
    recall: dict[str, list[float]] = dataclasses.field(default_factory=dict)
    #: label -> [CommReport per n]: honest per-strategy communication
    #: accounting — the paper's logical n*d*R bits next to the bytes the
    #: wire actually gathers (measured from the encode stage's payload
    #: shapes at the bucket the sweep ran; see ``distributed.CommReport``).
    #: ``collectives`` counts the per-trial wire collectives — 0 unless the
    #: sweep ran the distributed trial plane (a ("data","model") mesh).
    comm: dict[str, list[CommReport]] = dataclasses.field(default_factory=dict)
    #: n -> padded bucket the weights stage actually compiled for
    buckets: dict[int, int] = dataclasses.field(default_factory=dict)
    #: module compile-cache entries live after this sweep (see
    #: :func:`compile_cache_size` / :func:`clear_compile_caches`)
    compile_cache_size: int = 0
    #: total devices of the mesh the sweep ran under (1 = single-device
    #: vmap; on a 2-D wire mesh this is data * model — the rep axis
    #: shards over the "data" axis size only)
    mesh_devices: int = 1
    #: fault plans only: per-n REALIZED fault telemetry means, one dict per
    #: n in ``plan.ns`` — ``{"n", "dropped_machines", "straggling_machines",
    #: "retransmissions" (mean machines per retry round),
    #: "retry_rounds_used" (mean extra collectives per retry round)}`` —
    #: measured from the sweep's actual fault draws (the integer-exact
    #: telemetry channels ride the single host sync), never estimated from
    #: the plan's probabilities. ``None`` when ``plan.faults`` is None.
    faults: list[dict] | None = None
    #: memory-budget telemetry: ``{"memory_budget_bytes", "d_tile",
    #: "n_chunk", "metrics_chunk"}`` — the streaming knobs the sweep
    #: actually ran with (None values = monolithic). Empty for paths that
    #: predate the budget plumbing.
    tiling: dict = dataclasses.field(default_factory=dict)
    #: path plans only (``plan.path``): full-grid telemetry that rode the
    #: same single host sync as the selected-support metrics —
    #: ``{"select", "k", "lams" (label -> per-n mean grids),
    #: "error_rate" / "edge_f1" (label -> per-n per-lam curves),
    #: "iters" (label -> per-n mean solver iterations per lam — the
    #: warm-start early-exit savings made visible),
    #: "selected_hist" (label -> per-n selection counts per lam)}``.
    #: The headline ``error_rate``/``edge_f1``/... score the SELECTED
    #: support per trial. ``None`` for fixed-penalty plans.
    path: dict | None = None

    @property
    def trials_per_s(self) -> float:
        return self.plan.trials / max(self.seconds, 1e-9)


# --------------------------------------------------------------------------
# Host setup: stacked trees + trial keys (O(reps * d), cached per plan)
# --------------------------------------------------------------------------

def _draw_tree(kind: str, d: int, rng: np.random.Generator):
    if kind == "random":
        return trees.random_tree(d, rng)
    if kind == "star":
        return trees.star_tree(d)
    if kind == "chain":
        return trees.chain_tree(d)
    return list(trees.SKELETON_EDGES)


@functools.lru_cache(maxsize=None)
def _plan_setup(
    d: int, reps: int, tree: str, rho_min: float, rho_max: float, seed0: int
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Cached host-side sweep setup: (parents, rhos, adj_true, keys).

    Keyed on exactly the plan fields the ground truth depends on — NOT ns
    / strategies / buckets — so repeated ``run_trials`` calls on the same
    (or a re-scoped) plan skip the O(reps * d) Pruefer/BFS host loop and
    the per-rep key folds entirely. A miss records one
    ``repro.sweep.draw`` span.
    """
    with span("sweep.draw"):
        parents = np.zeros((reps, d), np.int32)
        rhos = np.zeros((reps, d), np.float32)
        for rep in range(reps):
            rng = np.random.default_rng(seed0 + rep)
            edges = _draw_tree(tree, d, rng)
            w = rng.uniform(rho_min, rho_max, size=d - 1)
            parents[rep], rhos[rep], _ = trees.topological_parents(d, edges, w)
        parents_j = jnp.asarray(parents)
        rhos_j = jnp.asarray(rhos)
        adj_true = trees.adjacency_from_parents(parents_j)
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.key(seed0), jnp.arange(reps, dtype=jnp.uint32))
        return parents_j, rhos_j, adj_true, keys


def _setup_key(plan: TrialPlan):
    return (plan.d, plan.reps, plan.tree,
            plan.rho_min, plan.rho_max, plan.seed0)


def stacked_trees(
    plan: TrialPlan,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The plan's ``reps`` ground-truth trees as stacked device arrays.

    Returns ``(parents, rhos, adj_true)`` of shapes (reps, d), (reps, d)
    and (reps, d, d): the topological parent form each trial samples from
    and the true adjacency each trial's estimate is scored against.
    Cached per plan (with the trial keys) — see :func:`_plan_setup`.
    Sparse plans have no tree ground truth — use
    :func:`sparse_ground_truth`.
    """
    if plan.structure == "sparse":
        raise ValueError(
            "sparse plans draw precision-matrix ground truths, not trees; "
            "use sparse_ground_truth(plan)")
    return _plan_setup(*_setup_key(plan))[:3]


def trial_keys(plan: TrialPlan) -> jax.Array:
    """(reps,) PRNG keys: one independent sampling stream per trial.
    Served from the same per-plan cache as :func:`stacked_trees` (or the
    sparse setup cache for sparse plans)."""
    if plan.structure == "sparse":
        return _sparse_plan_setup(*_sparse_setup_key(plan))[2]
    return _plan_setup(*_setup_key(plan))[3]


@functools.lru_cache(maxsize=None)
def _sparse_plan_setup(
    d: int, reps: int, density: float, rho_min: float, rho_max: float,
    seed0: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Cached host-side SPARSE sweep setup: (chols, adj_true, keys).

    Trial ``rep`` draws its ground truth from
    ``np.random.default_rng(seed0 + rep)`` — a random sparse
    diagonally-dominant precision (``glasso.random_sparse_precision``,
    edge strength Uniform[rho_min, rho_max]) — exactly mirroring the tree
    plane's per-rep rng convention. ``chols`` are the (reps, d, d)
    float32 Cholesky factors of the implied unit-variance covariances
    (the row-keyed sampler's mixers); ``adj_true`` the (reps, d, d) bool
    supports; ``keys`` the same per-rep fold_in streams as
    :func:`_plan_setup` (and, like it, one ``repro.sweep.draw`` span a
    miss).
    """
    with span("sweep.draw"):
        chols = np.zeros((reps, d, d), np.float32)
        adj = np.zeros((reps, d, d), bool)
        for rep in range(reps):
            rng = np.random.default_rng(seed0 + rep)
            theta = glasso.random_sparse_precision(
                d, density, rng, strength=(rho_min, rho_max))
            cov = np.linalg.inv(theta)
            chols[rep] = np.linalg.cholesky(cov)
            a = np.abs(theta) > 1e-8
            np.fill_diagonal(a, False)
            adj[rep] = a
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.key(seed0), jnp.arange(reps, dtype=jnp.uint32))
        return jnp.asarray(chols), jnp.asarray(adj), keys


def _sparse_setup_key(plan: TrialPlan):
    return (plan.d, plan.reps, plan.density,
            plan.rho_min, plan.rho_max, plan.seed0)


def sparse_ground_truth(plan: TrialPlan) -> tuple[jax.Array, jax.Array]:
    """The sparse plan's ``reps`` ground truths as stacked device arrays:
    ``(chols, adj_true)`` of shapes (reps, d, d) each — the Cholesky
    mixers the trials sample through and the true supports they are
    scored against. Cached per plan (with the trial keys)."""
    return _sparse_plan_setup(*_sparse_setup_key(plan))[:2]


# --------------------------------------------------------------------------
# Compiled stages (cached per strategy-set / bucket; ONE metric stage)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _weights_stage(
    strategies: tuple[Strategy, ...], n_pad: int, engine: GramEngine,
    faults: FaultPlan | None = None,
):
    """jit: (keys, parents, rhos, n_valid) -> (S, reps, d, d) weights.

    ONE launch samples the shared (reps, n_pad, d) data and produces every
    strategy's weight tensor through the batched Gram entry points; the
    traced ``n_valid`` masks the pad rows, so one compile per
    (strategy set, bucket) serves every n in the bucket.

    With a ``faults`` plan the signature is
    (keys, fault_keys, parents, rhos, n_valid) -> (weights, telemetry
    sums): the fault realization is drawn inside the launch (trial-keyed,
    bucket-stable) and the weights run the masked-Gram degradation path.

    Callers must pass a RESOLVED engine (never None): the closure is
    cached, so a baked-in None would pin whatever process default was
    live at first trace and silently ignore a later
    ``set_default_engine``. Call with ``faults`` POSITIONAL (None for the
    pristine wire) — lru_cache keys positional and keyword spellings
    separately.

    Budget-channel strategy sets grow a trailing ``rates`` operand — the
    stacked (S, d) per-feature rate vectors from
    :func:`_rates_operand` — so the per-n allocation stays a traced
    input (no recompile across the n sweep). The signature switch is
    static in ``strategies`` (part of the cache key), so gather-only
    sweeps keep the exact pre-channel signature.
    """
    if faults is None:
        if _needs_rates(strategies):
            def f(keys, parents, rhos, n_valid, rates):
                return _stacked_weights(
                    keys, parents, rhos, n_valid, strategies, n_pad, engine,
                    rates=rates)
        else:
            def f(keys, parents, rhos, n_valid):
                return _stacked_weights(
                    keys, parents, rhos, n_valid, strategies, n_pad, engine)
    else:
        if _needs_rates(strategies):
            def f(keys, fault_keys, parents, rhos, n_valid, rates):
                return _stacked_weights(
                    keys, parents, rhos, n_valid, strategies, n_pad, engine,
                    faults=faults, fault_keys=fault_keys, rates=rates)
        else:
            def f(keys, fault_keys, parents, rhos, n_valid):
                return _stacked_weights(
                    keys, parents, rhos, n_valid, strategies, n_pad, engine,
                    faults=faults, fault_keys=fault_keys)

    return jax.jit(f)


def _needs_rates(strategies) -> bool:
    """True when the strategy set carries a budget channel, i.e. the
    stage signatures grow the trailing stacked per-feature ``rates``
    operand (static in the strategies tuple, so it keys the jit/lru
    caches consistently)."""
    return any(s.channel.kind == "budget" for s in strategies)


def _rates_operand(strategies, n: int, d: int) -> jax.Array:
    """Stacked (S, d) int32 per-feature rate vectors for one sweep point.

    Budget strategies get their channel's greedy allocation at the TRUE
    sample count n (``BudgetChannel.column_rates``); every other strategy
    row is a constant fill at its own rate (never consulted — the slot
    keeps the stack rectangular). Host numpy -> one small device operand.
    """
    rows = [
        s.channel.column_rates(n, d, s.rate)
        if s.channel.kind == "budget"
        else np.full(d, s.rate, np.int32)
        for s in strategies
    ]
    return jnp.asarray(np.stack(rows))


def _channel_operands(strategies, rates, faults, fault_keys, n_pad, n_valid):
    """Per-strategy estimator kwargs for the non-gather channels.

    Budget strategies receive their (d,) slice of the stacked ``rates``
    operand; MAC strategies under a fault plan receive the (t, machines)
    delivered-row counts drawn from the SAME per-trial fault stream as
    the feature-block view (``FaultPlan.draw_rowblock_batch``), computed
    once per distinct machine count. Gather strategies get ``{}`` — their
    estimator calls are textually identical to the pre-channel engine.
    """
    ops: list[dict] = [{} for _ in strategies]
    delivered: dict[int, jax.Array] = {}
    for i, s in enumerate(strategies):
        kind = s.channel.kind
        if kind == "budget":
            ops[i] = {"rates": rates[i]}
        elif kind == "mac" and faults is not None:
            m = s.channel.machines
            if m not in delivered:
                delivered[m] = faults.draw_rowblock_batch(
                    fault_keys, n_pad, n_valid, m)
            ops[i] = {"delivered": delivered[m]}
    return ops


def _stacked_weights(keys, parents, rhos, n_valid, strategies, n_pad, engine,
                     faults=None, fault_keys=None, rates=None):
    """Shared trace body of the single-device and sharded weights stages:
    sample the bucket-shaped data once, emit every strategy's (r, d, d)
    weight tensor stacked as (S, r, d, d).

    With a fault plan the shared fault realization (one draw per trial,
    shared by every strategy — methods degrade on the SAME faults, the
    fault twin of the shared-data convention) masks each strategy's
    payload and the return is ``(weights, (channels,) telemetry sums)``.
    Channel operands (budget rate vectors, MAC delivered-row counts) ride
    per strategy via :func:`_channel_operands`.
    """
    x = sampler.sample_tree_ggm_rows_batch(keys, n_pad, parents, rhos)
    if faults is None:
        ops = _channel_operands(strategies, rates, None, None, n_pad, n_valid)
        return jnp.stack([
            estimators.strategy_weights_batch(
                x, s, n_valid=n_valid, engine=engine, **ops[i])
            for i, s in enumerate(strategies)])
    n_rows, flip, tele = faults.draw_batch(
        fault_keys, n_pad, n_valid, x.shape[-1])
    ops = _channel_operands(
        strategies, rates, faults, fault_keys, n_pad, n_valid)
    w = jnp.stack([
        estimators.strategy_weights_batch(
            x, s, n_valid=n_valid, n_rows=n_rows, flip=flip, engine=engine,
            **ops[i])
        for i, s in enumerate(strategies)])
    return w, tele.sum(axis=0)


def structure_metric_channels(
    adj_est: jax.Array, adj_ref: jax.Array
) -> jax.Array:
    """(..., d, d) estimated vs reference adjacencies -> (..., 3)
    [error, hamming, shared-edge] channels.

    All three channels are INTEGER-VALUED f32 (the error indicator, the
    edge symmetric difference, and |E_hat & E_ref| — for spanning trees
    edge F1 is exactly shared/(d-1)), so their sums are exact in f32
    under any reduction order: a psum over a sharded rep axis reproduces
    the single-device sums bit for bit — the distributed trial plane's
    parity gate. The serving plane reuses the same channels against the
    PREVIOUS solve: the hamming channel is the per-tenant structure-drift
    counter, shared is the stable-edge count.
    """
    adj_est = jnp.asarray(adj_est)
    adj_ref = jnp.asarray(adj_ref)
    err = trees.structure_error(adj_est, adj_ref).astype(jnp.float32)
    ham = trees.structure_hamming(adj_est, adj_ref).astype(jnp.float32)
    shared = jnp.sum(adj_est & adj_ref, axis=(-2, -1)).astype(
        jnp.float32) / 2  # symmetric adjacencies: exact integer halves
    return jnp.stack([err, ham, shared], axis=-1)


def _per_trial_metrics(w: jax.Array, adj_true: jax.Array,
                       chunk: int | None = None) -> jax.Array:
    """(S, r, d, d) weights + (r, d, d) truth -> (S, r, 3) per-trial
    [error, hamming, shared-edge count] via one flattened vmapped Boruvka
    solve; channels are :func:`structure_metric_channels` against truth.

    ``chunk`` (``TrialPlan.metrics_chunk``) streams the flattened trial
    stack through the solver in slabs instead of one full vmap — same
    bits per trial (``chow_liu.boruvka_mst_batch``), bounded working set.
    """
    S, r, d, _ = w.shape
    est = boruvka_mst_batch(w.reshape(S * r, d, d), chunk).reshape(S, r, d, d)
    return structure_metric_channels(est, adj_true[None])


@functools.lru_cache(maxsize=None)
def _mst_metrics_fn(chunk: int | None = None):
    """jit: (S, reps, d, d) weights + true adjacencies -> (S, 3) metric
    SUMS over the rep axis.

    One compile covers every point of every sweep in the process — the
    MWST + metric stage only sees (S, reps, d, d) shapes, which bucketing
    leaves untouched. Sums (not means) so the sharded path can psum the
    same quantity; the engine divides by reps once at the end. ``chunk``
    is the plan's memory-budgeted solve slab (``None`` = full vmap).
    """
    return jax.jit(
        lambda w, adj_true: _per_trial_metrics(w, adj_true, chunk)
        .sum(axis=1))


#: (S, reps, d) metric-stage shapes already compiled this process — guards
#: the cold-sweep prewarm so warm sweeps never pay the dummy launch.
_warmed_metric_shapes: set[tuple[int, int, int]] = set()

#: (strategies, bucket, engine, structure) stage keys already prewarmed —
#: guards the cross-bucket compile overlap so warm sweeps never spawn the
#: dummy executions.
_warmed_weight_stages: set = set()


# --------------------------------------------------------------------------
# Sparse trial plane stages (the §7 extension: glasso over quantized data)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _corr_stage(
    strategies: tuple[Strategy, ...], n_pad: int, engine: GramEngine,
    faults: FaultPlan | None = None,
):
    """jit: (keys, chols, n_valid) -> (S, reps, d, d) correlation
    statistics — the sparse twin of :func:`_weights_stage` (same bucketing
    and caching contract, including the faulty (keys, fault_keys, ...) ->
    (corr, telemetry sums) signature; the tail is
    ``estimators.corr_from_gram`` instead of the Chow-Liu weights).
    Budget-channel strategy sets grow the same trailing stacked ``rates``
    operand as :func:`_weights_stage`."""
    if faults is None:
        if _needs_rates(strategies):
            def f(keys, chols, n_valid, rates):
                return _stacked_corr(
                    keys, chols, n_valid, strategies, n_pad, engine,
                    rates=rates)
        else:
            def f(keys, chols, n_valid):
                return _stacked_corr(
                    keys, chols, n_valid, strategies, n_pad, engine)
    else:
        if _needs_rates(strategies):
            def f(keys, fault_keys, chols, n_valid, rates):
                return _stacked_corr(
                    keys, chols, n_valid, strategies, n_pad, engine,
                    faults=faults, fault_keys=fault_keys, rates=rates)
        else:
            def f(keys, fault_keys, chols, n_valid):
                return _stacked_corr(
                    keys, chols, n_valid, strategies, n_pad, engine,
                    faults=faults, fault_keys=fault_keys)

    return jax.jit(f)


def _stacked_corr(keys, chols, n_valid, strategies, n_pad, engine,
                  faults=None, fault_keys=None, rates=None):
    """Shared trace body of the single-device and sharded sparse stages:
    sample the bucket-shaped data once through the row-keyed generic
    sampler, emit every strategy's (r, d, d) correlation statistic (with a
    fault plan: the masked-Gram statistic + telemetry sums, mirroring
    :func:`_stacked_weights`, channel operands included)."""
    x = sampler.sample_ggm_rows_batch(keys, n_pad, chols)
    if faults is None:
        ops = _channel_operands(strategies, rates, None, None, n_pad, n_valid)
        return jnp.stack([
            estimators.strategy_corr_batch(
                x, s, n_valid=n_valid, engine=engine, **ops[i])
            for i, s in enumerate(strategies)])
    n_rows, flip, tele = faults.draw_batch(
        fault_keys, n_pad, n_valid, x.shape[-1])
    ops = _channel_operands(
        strategies, rates, faults, fault_keys, n_pad, n_valid)
    corr = jnp.stack([
        estimators.strategy_corr_batch(
            x, s, n_valid=n_valid, n_rows=n_rows, flip=flip, engine=engine,
            **ops[i])
        for i, s in enumerate(strategies)])
    return corr, tele.sum(axis=0)


def _support_metric_channels(est: jax.Array, adj_true: jax.Array) -> jax.Array:
    """(..., d, d) bool support estimates + truths -> (..., 5) channels
    [error, hamming, shared, est_edges, true_edges].

    All five are INTEGER-VALUED f32 (error indicator, support symmetric
    difference, and the :func:`trees.edge_counts` triple), so their sums
    are exact in f32 under any reduction order — precision, recall and
    micro-F1 are recovered EXACTLY from the reduced sums
    (P = shared/est, R = shared/true, F1 = 2*shared/(est+true)),
    generalizing the spanning-tree-only ``F1 = shared/(d-1)`` identity of
    the tree plane. This is the sparse parity gate's foundation: a psum
    over a sharded rep axis reproduces the single-device sums bit for bit.
    """
    err = trees.structure_error(est, adj_true).astype(jnp.float32)
    ham = trees.structure_hamming(est, adj_true).astype(jnp.float32)
    shared, n_est, n_true = trees.edge_counts(est, adj_true)
    return jnp.stack([err, ham, shared.astype(jnp.float32),
                      n_est.astype(jnp.float32),
                      n_true.astype(jnp.float32)], axis=-1)


def _sparse_per_trial_metrics(
    corr: jax.Array, adj_true: jax.Array, lams: tuple, tol: float,
    n_steps: int, chunk: int | None = None,
) -> jax.Array:
    """(S, r, d, d) correlation statistics + (r, d, d) truths -> (S, r, 5)
    per-trial support channels via ONE fused batched-glasso launch: the
    whole (S*r, d, d) stack solves in a single vmapped ISTA loop
    (per-strategy penalties ride as a batched lam vector), the support is
    thresholded on normalized partial correlations on device. ``chunk``
    streams the solve in slabs (``glasso_batch(chunk=...)``) where the
    plan's memory budget demands it — bit-identical per trial."""
    S, r, d, _ = corr.shape
    lam = jnp.repeat(jnp.asarray(lams, jnp.float32), r)
    theta = glasso.glasso_batch(
        corr.reshape(S * r, d, d), lam, n_steps=n_steps, chunk=chunk)
    est = glasso.support_from_theta(theta, tol).reshape(S, r, d, d)
    return _support_metric_channels(est, adj_true[None])


@functools.lru_cache(maxsize=None)
def _sparse_metrics_fn(lams: tuple, tol: float, n_steps: int,
                       chunk: int | None = None):
    """jit: (S, reps, d, d) correlation statistics + true supports ->
    (S, 5) metric SUMS over the rep axis — the sparse twin of
    :func:`_mst_metrics_fn` (glasso solve + support threshold instead of
    Boruvka; one compile per (penalty vector, tol, steps, chunk) serves
    every point of every sweep at that shape)."""
    return jax.jit(
        lambda corr, adj_true: _sparse_per_trial_metrics(
            corr, adj_true, lams, tol, n_steps, chunk).sum(axis=1))


@functools.lru_cache(maxsize=None)
def _sparse_path_metrics_fn(path: PathPlan, tol: float, n_steps: int,
                            chunk: int | None = None):
    """jit: (S, reps, d, d) correlation statistics + true supports +
    ``n_valid`` -> the path plane's device-resident metric bundle.

    The solve stage is ONE warm-started fused grid scan over the whole
    (S*reps, d, d) stack (``path.glasso_path_batch`` — same ``chunk``
    slab streaming as ``glasso_batch``), followed by on-device model
    selection (EBIC per trial, or StARS per strategy with the rep axis as
    the subsample batch). Everything returned is a SUM of integer-valued
    f32 channels over the rep axis — exact under any reduction order, so
    mesh-gathered statistics reproduce single-device results bit for bit
    (the sparse parity contract) — and the whole bundle rides the sweep's
    single host sync:

      * selected  (S, 5)    selected-support channel sums (the headline)
      * per_lam   (S, K, 5) full-path channel sums per lam
      * iters     (S, K)    solver-iteration sums (early-exit telemetry)
      * hist      (S, K)    selected-lam counts
      * lam_sums  (S, K)    grid sums (mean grid after /reps — derived
                            grids vary per trial statistic)
    """

    def f(corr, adj_true, n_valid):
        S_, r, d, _ = corr.shape
        flat = corr.reshape(S_ * r, d, d)
        lams = path_engine.path_lambdas(path, flat)          # (S*r, K)
        K = lams.shape[-1]
        solve = path_engine.glasso_path_batch(
            flat, lams, n_steps=n_steps, conv_tol=path.conv_tol,
            support_tol=tol, chunk=chunk)
        sup = solve.support.reshape(K, S_, r, d, d)
        ch = _support_metric_channels(sup, adj_true[None, None])  # (K,S,r,5)
        per_lam = jnp.swapaxes(ch.sum(axis=2), 0, 1)         # (S, K, 5)
        if path.select == "ebic":
            scores = path_engine.ebic_scores(
                solve.logdet, solve.tr_s_theta, solve.edges,
                n_valid, d, path.ebic_gamma)                 # (K, S*r)
            idx = path_engine.select_ebic(scores)            # (S*r,)
        else:
            # strategies select independently; their reps are the
            # StARS subsample batch
            xi = jax.vmap(path_engine.stars_instability,
                          in_axes=1, out_axes=1)(sup)        # (K, S)
            idx = jnp.repeat(
                path_engine.select_stars(xi, path.stars_beta), r)
        chf = ch.reshape(K, S_ * r, 5)
        sel = jnp.take_along_axis(
            chf, idx[None, :, None], axis=0)[0]              # (S*r, 5)
        selected = sel.reshape(S_, r, 5).sum(axis=1)         # (S, 5)
        hist = jax.nn.one_hot(idx, K, dtype=jnp.float32).reshape(
            S_, r, K).sum(axis=1)                            # (S, K)
        iters = jnp.swapaxes(
            solve.iters.reshape(K, S_, r).sum(axis=2), 0, 1) # (S, K)
        lam_sums = lams.reshape(S_, r, K).sum(axis=1)        # (S, K)
        return (selected, per_lam, iters.astype(jnp.float32), hist,
                lam_sums)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _sparse_sharded_corr_fn(
    strategies: tuple[Strategy, ...],
    n_pad: int,
    engine: GramEngine,
    mesh: Mesh,
    data_axis: str,
    faults: FaultPlan | None = None,
):
    """jit(shard_map): the SPARSE corr stage with the rep axis sharded
    over ``data_axis`` — emits the (S, reps, d, d) correlation statistics
    (rep-sharded on the way out; with a fault plan also the psum-reduced
    telemetry sums, replicated).

    The sparse mesh paths deliberately end the shard_map at the
    correlation statistic: it is bit-stable across shardings
    (integer-exact sign Grams, batch-stable eigh — verified by the parity
    gate), while the ISTA loop's fused reductions are
    compilation-context-sensitive. ``run_trials`` gathers the statistics
    to one device and runs the SAME compiled solve+metric stage as the
    mesh-less engine, making mesh results bit-identical by construction.
    """
    needs_rates = _needs_rates(strategies)
    rates_spec = (P(),) if needs_rates else ()
    if faults is None:
        def body(key_data, chols, n_valid, *tail):
            keys = jax.random.wrap_key_data(key_data)
            return _stacked_corr(
                keys, chols, n_valid, strategies, n_pad, engine,
                rates=tail[0] if needs_rates else None)

        in_specs = (P(data_axis), P(data_axis), P()) + rates_spec
        out_specs = P(None, data_axis)
    else:
        def body(key_data, fkey_data, chols, n_valid, *tail):
            keys = jax.random.wrap_key_data(key_data)
            fkeys = jax.random.wrap_key_data(fkey_data)
            corr, tele = _stacked_corr(
                keys, chols, n_valid, strategies, n_pad, engine,
                faults=faults, fault_keys=fkeys,
                rates=tail[0] if needs_rates else None)
            # integer-valued channels: the psum is exact, so telemetry is
            # shard-count invariant like the metric sums
            return corr, jax.lax.psum(tele, data_axis)

        in_specs = (P(data_axis), P(data_axis), P(data_axis), P()) \
            + rates_spec
        out_specs = (P(None, data_axis), P())

    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ))


def _check_mac_rowsplit(strategies, n_pad: int, n_model: int) -> None:
    """Wire-plane MAC strategies split the SAMPLE axis over the model
    mesh axis (each rank contracts its row share of the superposition),
    so the bucket must divide evenly — both are powers of two in every
    supported configuration, so this only trips hand-rolled buckets."""
    if n_pad % n_model and any(s.channel.kind == "mac" for s in strategies):
        raise ValueError(
            f"MAC channel strategies need the sample bucket to split over "
            f"the model mesh axis: n_pad={n_pad} is not a multiple of "
            f"n_model={n_model}")


def _mac_wire_stat(s, plan, x, midx, n_model, n_pad, n_valid, flip, fkeys,
                   faults, engine, delivered_by_m, *, corr):
    """One MAC-channel strategy's statistic inside a wire-plane shard_map
    body. Every rank masks the FULL replicated sample block down to the
    delivered machine row-blocks (deterministic from the replicated fault
    keys, so ranks agree bit for bit), contracts ITS row share of the
    superposition, and ``plan.wire`` — ``comm.superposed_psum``, the
    multiple-access channel — adds the partial sign-Grams over the model
    axis. Sign Grams are integer-valued f32 well under 2^24, so ANY row
    partition (including the 1-rank mesh) sums to the same bits; the
    center then normalizes by the delivered-row effective counts
    (``plan.central_from_sum``). That integer-exactness is the 1-vs-N
    parity argument for this channel."""
    delivered = None
    if faults is not None:
        m = s.channel.machines
        if m not in delivered_by_m:
            delivered_by_m[m] = faults.draw_rowblock_batch(
                fkeys, n_pad, n_valid, m)
        delivered = delivered_by_m[m]
    u = estimators.mac_sign_codes(
        x, s, n_valid=n_valid, delivered=delivered, flip=flip)
    n_loc = n_pad // n_model
    u_loc = jax.lax.dynamic_slice_in_dim(u, midx * n_loc, n_loc, 1)
    part = resolve_engine(engine).gram_batch(u_loc)
    gram = plan.wire(part)
    n_eff = estimators.mac_effective_count(
        s, n_pad, n_valid=n_valid, delivered=delivered)
    return plan.central_from_sum(gram, n_eff, corr=corr)


def _budget_wire_stat(s, plan, x_loc, midx, d_loc, rates_row, n_valid,
                      n_rows, n_rows_loc, keep_loc, engine, *, corr):
    """One budget-channel strategy's statistic inside a wire-plane
    shard_map body. The rank encodes its feature block at the block's
    allocated per-feature rates (its slice of the replicated (d,) rate
    vector — per-feature encode commutes with feature slicing, so the
    gathered heterogeneous-rate payload is bit-identical to the
    single-device encode), then the center decodes through the
    rate-indexed centroid table; rate-0 features and erased machines both
    land on the masked code and zero out of the effective counts."""
    rates_loc = jax.lax.dynamic_slice_in_dim(
        rates_row, midx * d_loc, d_loc, 0)
    payload = plan.encode(x_loc, n_valid=n_valid, n_rows=n_rows_loc,
                          rates=rates_loc)
    full = plan.wire(payload, keep=keep_loc)
    return estimators.budget_estimate(
        full, s, rates_row, n_valid=n_valid, n_rows=n_rows, engine=engine,
        corr=corr)


@functools.lru_cache(maxsize=None)
def _sparse_wire_corr_fn(
    strategies: tuple[Strategy, ...],
    n_pad: int,
    engine: GramEngine,
    mesh: Mesh,
    data_axis: str,
    model_axis: str,
    faults: FaultPlan | None = None,
):
    """jit(shard_map): the SPARSE corr stage on the DISTRIBUTED trial
    plane — trials sharded over ``data_axis``, features over
    ``model_axis``, each trial running the paper's actual all-gather
    (``WirePlan.encode -> wire -> central_corr``).

    The gathered payload is bit-identical to the single-device encode of
    the unsliced data, so the emitted (S, reps, d, d) statistics equal the
    mesh-less corr stage bit for bit; the glasso solve + support metrics
    then run through the shared single-device executable (see
    :func:`_sparse_sharded_corr_fn` for why the solve stays outside the
    shard_map) — the sparse extension of the CI parity gate.

    With a fault plan every rank reconstructs the FULL fault realization
    from the replicated fault keys (deterministic — the ranks agree bit
    for bit, exactly like the replicated sampling), slices its feature
    block's faults, masks its payload machine-side, and the dropped
    features are ERASED on the wire itself
    (``comm.collectives.erasure_all_gather`` via ``WirePlan.wire(keep=)``).

    Non-gather channels swap the wire's middle stage: MAC strategies run
    :func:`_mac_wire_stat` (partial-Gram superposition), budget strategies
    :func:`_budget_wire_stat` (heterogeneous-rate encode; the stacked
    (S, d) rate vectors arrive as a replicated trailing operand).
    """
    n_model = mesh.shape[model_axis]
    needs_rates = _needs_rates(strategies)
    _check_mac_rowsplit(strategies, n_pad, n_model)

    def make_body(with_faults: bool):
        def body(key_data, *rest):
            if needs_rates:
                rest, rates_op = rest[:-1], rest[-1]
            else:
                rates_op = None
            if with_faults:
                fkey_data, chols, n_valid = rest
                fkeys = jax.random.wrap_key_data(fkey_data)
            else:
                chols, n_valid = rest
                fkeys = None
            keys = jax.random.wrap_key_data(key_data)
            x = sampler.sample_ggm_rows_batch(keys, n_pad, chols)
            d = x.shape[-1]
            d_loc = d // n_model
            midx = jax.lax.axis_index(model_axis)
            x_loc = jax.lax.dynamic_slice_in_dim(x, midx * d_loc, d_loc, 2)
            n = jnp.asarray(n_valid, jnp.float32)
            n_rows = flip = n_rows_loc = flip_loc = keep_loc = tele = None
            if with_faults:
                n_rows, flip, tele = faults.draw_batch(
                    fkeys, n_pad, n_valid, d)
                n_rows_loc = jax.lax.dynamic_slice_in_dim(
                    n_rows, midx * d_loc, d_loc, 1)
                if flip is not None:
                    flip_loc = jax.lax.dynamic_slice_in_dim(
                        flip, midx * d_loc, d_loc, 2)
                keep_loc = n_rows_loc > 0
            corrs = []
            delivered_by_m: dict = {}
            for i, s in enumerate(strategies):
                plan = WirePlan(s, data_axis=data_axis,
                                model_axis=model_axis, engine=engine)
                kind = s.channel.kind
                if kind == "mac":
                    corrs.append(_mac_wire_stat(
                        s, plan, x, midx, n_model, n_pad, n_valid, flip,
                        fkeys, faults if with_faults else None, engine,
                        delivered_by_m, corr=True))
                    continue
                if kind == "budget":
                    corrs.append(_budget_wire_stat(
                        s, plan, x_loc, midx, d_loc, rates_op[i], n_valid,
                        n_rows, n_rows_loc, keep_loc, engine, corr=True))
                    continue
                payload = plan.encode(x_loc, n_valid=n_valid,
                                      n_rows=n_rows_loc, flip=flip_loc)
                full = plan.wire(payload, keep=keep_loc)
                corrs.append(plan.central_corr(
                    full, n, n_valid=n_valid, n_rows=n_rows,
                    n_rows_own=n_rows_loc, own_payload=payload))
            out = jnp.stack(corrs)  # (S, r_loc, d, d)
            if with_faults:
                return out, jax.lax.psum(tele.sum(axis=0), data_axis)
            return out

        return body

    rates_spec = (P(),) if needs_rates else ()
    if faults is None:
        in_specs = (P(data_axis), P(data_axis), P()) + rates_spec
        out_specs = P(None, data_axis)
    else:
        in_specs = (P(data_axis), P(data_axis), P(data_axis), P()) \
            + rates_spec
        out_specs = (P(None, data_axis), P())

    return jax.jit(jax.shard_map(
        make_body(faults is not None),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _sharded_point_fn(
    strategies: tuple[Strategy, ...],
    n_pad: int,
    engine: GramEngine,
    mesh: Mesh,
    data_axis: str,
    faults: FaultPlan | None = None,
    chunk: int | None = None,
):
    """jit(shard_map): one sweep point with the rep axis sharded over
    ``data_axis``; metric sums psum-reduced, so the (S, 3) output is
    replicated and the host path is identical to the single-device one
    (with a fault plan the psum-reduced telemetry sums ride along — both
    integer-valued, so shard count cannot perturb either). ``chunk`` is
    the plan's memory-budgeted solve slab — per-trial-identical, so it
    cannot perturb the parity either; pass it (like ``faults``)
    POSITIONALLY for a consistent lru key.

    Trial keys travel as raw uint32 key data (``jax.random.key_data``) —
    typed key arrays predate stable shard_map support on some jax
    versions — and are re-wrapped per shard (default PRNG impl, matching
    ``jax.random.key`` in :func:`_plan_setup`).
    """
    needs_rates = _needs_rates(strategies)
    rates_spec = (P(),) if needs_rates else ()
    if faults is None:
        def body(key_data, parents, rhos, adj_true, n_valid, *tail):
            keys = jax.random.wrap_key_data(key_data)
            w = _stacked_weights(
                keys, parents, rhos, n_valid, strategies, n_pad, engine,
                rates=tail[0] if needs_rates else None)
            sums = _per_trial_metrics(w, adj_true, chunk).sum(axis=1)
            return jax.lax.psum(sums, data_axis)

        in_specs = (P(data_axis), P(data_axis), P(data_axis), P(data_axis),
                    P()) + rates_spec
        out_specs = P()
    else:
        def body(key_data, fkey_data, parents, rhos, adj_true, n_valid,
                 *tail):
            keys = jax.random.wrap_key_data(key_data)
            fkeys = jax.random.wrap_key_data(fkey_data)
            w, tele = _stacked_weights(
                keys, parents, rhos, n_valid, strategies, n_pad, engine,
                faults=faults, fault_keys=fkeys,
                rates=tail[0] if needs_rates else None)
            sums = _per_trial_metrics(w, adj_true, chunk).sum(axis=1)
            return (jax.lax.psum(sums, data_axis),
                    jax.lax.psum(tele, data_axis))

        in_specs = (P(data_axis), P(data_axis), P(data_axis), P(data_axis),
                    P(data_axis), P()) + rates_spec
        out_specs = (P(), P())

    # check_vma=False: the replication checker has no rule for the while
    # loop inside boruvka_mst (jax 0.4.x); the out spec is still honest —
    # the psum above replicates the sums by construction.
    return jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _wire_point_fn(
    strategies: tuple[Strategy, ...],
    n_pad: int,
    engine: GramEngine,
    mesh: Mesh,
    data_axis: str,
    model_axis: str,
    faults: FaultPlan | None = None,
    chunk: int | None = None,
):
    """jit(shard_map): one sweep point on the DISTRIBUTED trial plane —
    trials sharded over ``data_axis``, features over ``model_axis``.

    Each (data, model) rank samples its rep shard's full-feature data
    (replicated over the model axis — PRNG-deterministic, so every rank
    agrees bit for bit), slices out its feature block (its group of the
    paper's machines), and runs the stage-decomposed wire runtime per
    strategy: ``WirePlan.encode`` (local quantization of the slice) ->
    ``WirePlan.wire`` (THE all-gather the paper counts) ->
    ``WirePlan.central`` (Gram on the gathered payload + weights). The
    gathered payload is bit-identical to the single-device encode of the
    unsliced data, so weights, Boruvka trees, and the integer-exact
    psum-reduced metric sums all reproduce the single-device engine
    EXACTLY — the parity gate CI enforces on 1 vs 8 forced host devices.

    With a fault plan every rank reconstructs the FULL fault realization
    from the replicated fault keys, masks its own feature slice
    machine-side (``encode(n_rows=..., flip=...)``), ERASES dropped
    features on the wire itself (``wire(keep=...)`` —
    ``comm.collectives.erasure_all_gather``), and the center degrades
    through the masked-Gram path (``central(n_rows=...)``) — all
    deterministic, so fault-enabled metrics keep the 1-vs-N parity.

    Non-gather channels swap the wire's middle stage per strategy: MAC
    runs :func:`_mac_wire_stat` (row-share partial Grams superposed by
    ``comm.superposed_psum``), budget runs :func:`_budget_wire_stat`
    (heterogeneous per-feature rates from the replicated trailing
    ``rates`` operand). Both stay inside the same shard_map and the same
    psum-reduced metric sums, so the parity gate covers all channels.
    """
    n_model = mesh.shape[model_axis]
    needs_rates = _needs_rates(strategies)
    _check_mac_rowsplit(strategies, n_pad, n_model)

    def make_body(with_faults: bool):
        def body(key_data, *rest):
            if needs_rates:
                rest, rates_op = rest[:-1], rest[-1]
            else:
                rates_op = None
            if with_faults:
                fkey_data, parents, rhos, adj_true, n_valid = rest
                fkeys = jax.random.wrap_key_data(fkey_data)
            else:
                parents, rhos, adj_true, n_valid = rest
                fkeys = None
            keys = jax.random.wrap_key_data(key_data)
            x = sampler.sample_tree_ggm_rows_batch(keys, n_pad, parents,
                                                   rhos)
            d = x.shape[-1]
            d_loc = d // n_model
            midx = jax.lax.axis_index(model_axis)
            x_loc = jax.lax.dynamic_slice_in_dim(x, midx * d_loc, d_loc, 2)
            n = jnp.asarray(n_valid, jnp.float32)
            n_rows = flip = n_rows_loc = flip_loc = keep_loc = tele = None
            if with_faults:
                n_rows, flip, tele = faults.draw_batch(
                    fkeys, n_pad, n_valid, d)
                n_rows_loc = jax.lax.dynamic_slice_in_dim(
                    n_rows, midx * d_loc, d_loc, 1)
                if flip is not None:
                    flip_loc = jax.lax.dynamic_slice_in_dim(
                        flip, midx * d_loc, d_loc, 2)
                keep_loc = n_rows_loc > 0
            ws = []
            delivered_by_m: dict = {}
            for i, s in enumerate(strategies):
                plan = WirePlan(s, data_axis=data_axis,
                                model_axis=model_axis, engine=engine)
                kind = s.channel.kind
                if kind == "mac":
                    ws.append(_mac_wire_stat(
                        s, plan, x, midx, n_model, n_pad, n_valid, flip,
                        fkeys, faults if with_faults else None, engine,
                        delivered_by_m, corr=False))
                    continue
                if kind == "budget":
                    ws.append(_budget_wire_stat(
                        s, plan, x_loc, midx, d_loc, rates_op[i], n_valid,
                        n_rows, n_rows_loc, keep_loc, engine, corr=False))
                    continue
                payload = plan.encode(x_loc, n_valid=n_valid,
                                      n_rows=n_rows_loc, flip=flip_loc)
                full = plan.wire(payload, keep=keep_loc)
                ws.append(plan.central(
                    full, n, n_valid=n_valid, n_rows=n_rows,
                    n_rows_own=n_rows_loc, own_payload=payload))
            w = jnp.stack(ws)
            sums = _per_trial_metrics(w, adj_true, chunk).sum(axis=1)
            # exact: integer-valued f32 sums; replicated over the model
            # axis by construction (every rank holds the full gathered
            # payload, the gathered row blocks, or the psum-superposed
            # Gram sum)
            if with_faults:
                return (jax.lax.psum(sums, data_axis),
                        jax.lax.psum(tele.sum(axis=0), data_axis))
            return jax.lax.psum(sums, data_axis)

        return body

    rates_spec = (P(),) if needs_rates else ()
    if faults is None:
        in_specs = (P(data_axis), P(data_axis), P(data_axis), P(data_axis),
                    P()) + rates_spec
        out_specs = P()
    else:
        in_specs = (P(data_axis), P(data_axis), P(data_axis), P(data_axis),
                    P(data_axis), P()) + rates_spec
        out_specs = (P(), P())

    return jax.jit(jax.shard_map(
        make_body(faults is not None),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    ))


# --------------------------------------------------------------------------
# Compile-cache hygiene (satellite: bound long-lived sweep services)
# --------------------------------------------------------------------------

def _compile_caches():
    return (_plan_setup, _weights_stage, _mst_metrics_fn, _sharded_point_fn,
            _wire_point_fn, _sparse_plan_setup, _corr_stage,
            _sparse_metrics_fn, _sparse_path_metrics_fn,
            _sparse_sharded_corr_fn, _sparse_wire_corr_fn, _crossover_fn,
            _corr_err_fn, _wire_runtime)


def compile_cache_size() -> int:
    """Total live entries across this module's compile/setup caches (each
    entry pins a jitted executable or a per-plan device-array bundle)."""
    return sum(c.cache_info().currsize for c in _compile_caches())


def clear_compile_caches() -> int:
    """Drop every cached compiled stage and per-plan setup bundle.

    The module caches are unbounded by design (sweeps re-enter the same
    shapes constantly); a long-lived process cycling through many distinct
    (strategy set, bucket) combinations can call this to release the
    executables and device arrays they pin. Returns the number of entries
    released.
    """
    n = compile_cache_size()
    for c in _compile_caches():
        c.cache_clear()
    _warmed_metric_shapes.clear()
    _warmed_weight_stages.clear()
    return n


# --------------------------------------------------------------------------
# The sweep engine
# --------------------------------------------------------------------------

def _comm_reports(
    plan: TrialPlan, engine: GramEngine, data_axis: str, model_axis: str,
    wire_plane: bool, fault_sums: np.ndarray | None = None,
) -> dict[str, list[CommReport]]:
    """Per-strategy CommReport per n: logical n*d*R bits (true n) next to
    the wire bytes the encode stage's payload actually occupies at the
    bucket the sweep gathered. Collective counts apply only when the wire
    runtime really ran (the distributed trial plane).

    ``fault_sums`` — the sweep's (len(ns), channels) realized telemetry
    sums (fault plans with retries): retry bytes are MEASURED from the
    realized retransmission counts — mean machines re-requested per retry
    round times the per-machine wire bytes (machines divide d into equal
    feature blocks, so every machine's payload is exactly wire_bytes /
    machines) — never estimated from the dropout probability.
    """
    f = plan.faults
    comm: dict[str, list[CommReport]] = {}
    for s in plan.strategies:
        wp = WirePlan(s, data_axis=data_axis, model_axis=model_axis,
                      engine=engine)
        reports = []
        for i, n in enumerate(plan.ns):
            rep = wp.comm_report(n, plan.d, n_pad=plan.bucket_for(n))
            if not wire_plane:
                rep = dataclasses.replace(rep, collectives=0)
            if f is not None and f.retries > 0 and fault_sums is not None:
                machines = f.n_machines(plan.d)
                retrans = fault_sums[i, 2:2 + f.retries] / plan.reps
                used = fault_sums[i, 2 + f.retries:2 + 2 * f.retries] \
                    / plan.reps
                rep = dataclasses.replace(
                    rep,
                    retry_bytes=float(np.sum(retrans))
                    * rep.wire_bytes / machines,
                    retry_collectives=float(np.sum(used)),
                    retry_rounds=f.retries)
            reports.append(rep)
        comm[s.label] = reports
    return comm


def _fault_stats(plan: TrialPlan,
                 fault_sums: np.ndarray | None) -> list[dict] | None:
    """(len(ns), channels) realized telemetry sums -> the per-n
    ``TrialResult.faults`` dicts (means over reps). Measured, not
    estimated: these are the integer-exact channel sums that rode the
    sweep's single host sync."""
    if fault_sums is None:
        return None
    r = plan.faults.retries
    stats = []
    for i, n in enumerate(plan.ns):
        row = np.asarray(fault_sums[i], np.float64) / plan.reps
        stats.append({
            "n": int(n),
            "dropped_machines": float(row[0]),
            "straggling_machines": float(row[1]),
            "retransmissions": [float(v) for v in row[2:2 + r]],
            "retry_rounds_used": [float(v) for v in row[2 + r:2 + 2 * r]],
        })
    return stats


def _path_stats(plan: TrialPlan, extras: tuple | None) -> dict | None:
    """Host packaging of the path plane's full-grid telemetry sums
    (per_lam, iters, hist, lam_sums — each (S, len(ns), K, ...)) into the
    ``TrialResult.path`` dict. Ratios of integer-exact channel sums, same
    arithmetic as the headline metrics."""
    if extras is None:
        return None
    per_lam, iters, hist, lam_sums = (np.asarray(e) for e in extras)
    reps = np.float32(plan.reps)
    labels = [s.label for s in plan.strategies]

    def _grid_cols(a: np.ndarray) -> dict[str, list[list[float]]]:
        # a: (S, len(ns), K) -> label -> per-n list of per-lam values
        return {lab: [[float(v) for v in row] for row in a[i]]
                for i, lab in enumerate(labels)}

    shared, n_est, n_true = (per_lam[:, :, :, 2], per_lam[:, :, :, 3],
                             per_lam[:, :, :, 4])
    return {
        "select": plan.path.select,
        "k": plan.path.k,
        "lams": _grid_cols(lam_sums / reps),
        "error_rate": _grid_cols(per_lam[:, :, :, 0] / reps),
        "edge_f1": _grid_cols(
            2.0 * shared / np.maximum(n_est + n_true, np.float32(1e-9))),
        "iters": _grid_cols(iters / reps),
        "selected_hist": _grid_cols(hist),
    }


def _package_result(
    plan: TrialPlan,
    m: np.ndarray,
    *,
    seconds: float,
    host_syncs: int,
    comm: dict[str, list[CommReport]],
    mesh_devices: int,
    faults: list[dict] | None = None,
    tiling: dict | None = None,
    path_telemetry: dict | None = None,
) -> TrialResult:
    """Mean-metric tensor -> TrialResult; shared by every engine path so
    the f32 arithmetic of the derived metrics is identical everywhere.

    Tree plans carry (S, len(ns), 3) channels [error, hamming, shared]
    (edge F1 == shared/(d-1) exactly for spanning trees); sparse plans
    (S, len(ns), 5) [error, hamming, shared, est_edges, true_edges], from
    which precision / recall / micro-F1 are recovered exactly
    (P = shared/est, R = shared/true, F1 = 2*shared/(est+true) — ratios of
    integer-exact channel means)."""
    labels = [s.label for s in plan.strategies]

    def _cols(a: np.ndarray) -> dict[str, list[float]]:
        return {lab: [float(v) for v in a[i]] for i, lab in enumerate(labels)}

    error_rate = _cols(m[:, :, 0])
    edit_distance = _cols(m[:, :, 1])
    if plan.structure == "sparse":
        shared, n_est, n_true = m[:, :, 2], m[:, :, 3], m[:, :, 4]
        precision = _cols(shared / np.maximum(n_est, np.float32(1e-9)))
        recall = _cols(shared / np.maximum(n_true, np.float32(1e-9)))
        edge_f1 = _cols(2.0 * shared
                        / np.maximum(n_est + n_true, np.float32(1e-9)))
    else:
        # Boruvka/Kruskal estimates and the ground truth are spanning
        # trees, so edge F1 == shared edges / (d - 1) exactly (same f32
        # division on both paths) — and est == true == d-1 makes
        # precision == recall == F1.
        edge_f1 = _cols(m[:, :, 2] / np.float32(plan.d - 1))
        precision = {lab: list(v) for lab, v in edge_f1.items()}
        recall = {lab: list(v) for lab, v in edge_f1.items()}
    return TrialResult(
        plan=plan, error_rate=error_rate, edit_distance=edit_distance,
        edge_f1=edge_f1, precision=precision, recall=recall,
        seconds=seconds, host_syncs=host_syncs, comm=comm,
        buckets=plan.buckets, compile_cache_size=compile_cache_size(),
        mesh_devices=mesh_devices, faults=faults, tiling=tiling or {},
        path=path_telemetry)


def _host_kruskal_trials(
    plan: TrialPlan, engine: GramEngine, data_axis: str, model_axis: str,
    t0: float,
) -> TrialResult:
    """The ``mst="host_kruskal"`` escape hatch: device weights stage, host
    MWST + metrics.

    Every (n, strategy, rep) weight matrix is computed by the SAME
    compiled weights stage as the device path, stacked across ns ((S, r,
    d, d) is n-independent) and read back in ONE ``jax.device_get`` —
    host_syncs stays 1 — then the host loop runs ``kruskal_mst`` (the
    paper's §3 solver) and numpy metrics per trial. Metric-identical to
    the device Boruvka path while the two solvers are rank-equivalent;
    the hatch exists for future solvers that break that equivalence.
    """
    parents, rhos, adj_true, keys = _plan_setup(*_setup_key(plan))
    faults = plan.faults
    fkeys = (fault_trial_keys(faults, plan.reps)
             if faults is not None else None)
    lead = () if faults is None else (fkeys,)
    ws = []
    fsums = []
    needs_rates = _needs_rates(plan.strategies)
    for n in plan.ns:
        n_pad = plan.bucket_for(n)
        tail = ((_rates_operand(plan.strategies, n, plan.d),)
                if needs_rates else ())
        out = _weights_stage(plan.strategies, n_pad, engine, faults)(
            keys, *lead, parents, rhos, jnp.asarray(n, jnp.int32), *tail)
        if faults is None:
            ws.append(out)
        else:
            ws.append(out[0])
            fsums.append(out[1])
    stacked = jnp.stack(ws)  # (len(ns), S, reps, d, d)
    host_f = None
    if faults is None:
        host_w, host_adj = jax.device_get(
            jax.block_until_ready((stacked, adj_true)))
    else:  # the telemetry rides the SAME single read-back
        host_w, host_adj, host_f = jax.device_get(
            jax.block_until_ready((stacked, adj_true, jnp.stack(fsums))))
    syncs = 1
    d = plan.d
    sums = np.zeros((len(plan.strategies), len(plan.ns), 3), np.float32)
    for i_n in range(len(plan.ns)):
        for i_s in range(len(plan.strategies)):
            for rep in range(plan.reps):
                est = np.zeros((d, d), dtype=bool)
                for j, k in kruskal_mst(host_w[i_n, i_s, rep]):
                    est[j, k] = est[k, j] = True
                true = host_adj[rep]
                sums[i_s, i_n, 0] += (est != true).any()
                sums[i_s, i_n, 1] += (est != true).sum() // 2
                sums[i_s, i_n, 2] += (est & true).sum() // 2
    m = sums / np.float32(plan.reps)
    seconds = time.perf_counter() - t0
    comm = _comm_reports(plan, engine, data_axis, model_axis, False,
                         fault_sums=host_f)
    return _package_result(plan, m, seconds=seconds, host_syncs=syncs,
                           comm=comm, mesh_devices=1,
                           faults=_fault_stats(plan, host_f),
                           tiling={"memory_budget_bytes":
                                   plan.effective_memory_budget,
                                   "d_tile": engine.d_tile,
                                   "n_chunk": engine.n_chunk,
                                   "metrics_chunk": None})


@spanned("run_trials")
def run_trials(
    plan: TrialPlan,
    *,
    engine: GramEngine | None = None,
    mesh: Mesh | None = None,
    data_axis: str = "data",
    model_axis: str = "model",
    mst: str = "device",
) -> TrialResult:
    """Execute a full Monte-Carlo sweep on device with ONE host sync.

    For each n the trial data (reps, n_bucket, d) is sampled ONCE and
    shared by every strategy (the reference loop's semantics: methods see
    the same draws). Per n the chain

        sample -> quantize -> Gram -> weights            (all strategies,
                                                          one launch)
        -> vmap(boruvka_mst) -> per-trial metrics -> sum (one (S*reps,
                                                          d, d) launch)

    runs as compiled device code over the whole trial axis; per-point
    metric sums accumulate on device and the ONLY host interaction of the
    whole sweep is the final (S, len(ns), 3) tensor read-back — an
    EXPLICIT ``jax.device_get``, so the sweep body stays clean under
    ``jax.transfer_guard_device_to_host("disallow")``.

    ``mst`` picks the MWST solver: ``"device"`` (default) is the on-device
    Boruvka — exact-equal to host Kruskal by the shared edge order
    (so a ``Strategy(mst='kruskal')`` measures identically here) —
    ``"host_kruskal"`` is the escape hatch for future solvers that break
    that order equivalence: the device weights are read back in one stacked
    ``device_get`` (host_syncs stays 1) and the MWST + metrics run as a
    host loop; metric-identical to the device path on the current
    estimators (pinned by test).

    Mesh modes (``plan.reps`` must divide the ``data_axis`` size; draws
    are keyed per (rep, row), so neither sharding nor bucketing can change
    any trial's data or recovered tree):

    * 1-D ``("data",)`` (``launch.mesh.make_trial_mesh()``) — the rep axis
      is shard_mapped over the data axis with psum-reduced metric sums.
    * 2-D ``("data", "model")`` (``make_trial_mesh(model=M)``) — the
      DISTRIBUTED trial plane: reps shard over data AND features over
      model (``plan.d % M == 0``), each trial running the stage-decomposed
      wire runtime (``distributed.WirePlan``: encode -> all-gather ->
      central) with the paper's actual collectives. Metric sums are
      integer-exact, so results are bit-identical to the single-device
      engine; ``TrialResult.comm`` carries each strategy's measured
      CommReport either way.

    SPARSE plans (``plan.structure == "sparse"``; see :class:`TrialPlan`)
    run the same modes with the Boruvka stage replaced by the batched
    device glasso + partial-correlation support threshold; under a mesh
    the shard_map ends at the correlation statistic and the solve+metric
    stage runs on one device through the same executable as the mesh-less
    engine (bit-identical results, still one host sync — the gather is a
    device_put). ``TrialResult.precision`` / ``recall`` join the metric
    tables (micro-averaged, exact from the integer channels).

    FAULT plans (``plan.faults``, a ``core.faults.FaultPlan``) inject
    deterministic machine dropout / straggler truncation / sign bit-flips
    into every mode: draws are trial/machine/round-keyed ``fold_in``
    streams (bucket- and shard-stable, like the sampler), the center
    degrades through the masked-Gram path (per-entry effective pairwise
    counts), and the realized telemetry rides the same single host sync
    onto ``TrialResult.faults`` (+ measured retry bits on the
    CommReports). A ZERO-fault plan still runs the fault path and is
    bit-identical to ``faults=None``; fault-enabled mesh runs keep the
    1-vs-N device parity (both pinned by CI).

    ``TrialResult.seconds`` runs from the top of the call, host tree
    draws included, to the packaging of the result: the interval of the
    call's ``repro.run_trials`` span. Inside it, each of ``plan.ns``
    records a ``repro.sweep.point`` span (stage lookup and dispatch), the
    read-back a ``repro.sweep.sync`` span and the host packaging after it
    a ``repro.sweep.report`` span.
    """
    t0 = time.perf_counter()
    engine = resolve_engine(engine)
    labels = [s.label for s in plan.strategies]
    if len(set(labels)) != len(labels):
        raise ValueError(f"duplicate strategy labels: {labels}")
    if mst not in ("device", "host_kruskal"):
        raise ValueError(f"unknown mst mode {mst!r}")
    # memory budget: clamp the engine's streaming knobs to the plan
    # (deterministic per (plan, engine) — mesh-parity-safe) and pick the
    # solve-stage slab
    engine = plan.budget_engine(engine)
    chunk = plan.metrics_chunk()
    sparse = plan.structure == "sparse"
    if mst == "host_kruskal":
        if mesh is not None:
            raise ValueError(
                "mst='host_kruskal' is the single-process escape hatch; "
                "run it without a mesh")
        if sparse:
            raise ValueError(
                "mst='host_kruskal' is a tree-plane escape hatch; sparse "
                "plans solve glasso, not an MWST")
        return _host_kruskal_trials(plan, engine, data_axis, model_axis, t0)
    shards = 1
    wire_plane = False
    if mesh is not None:
        shards = mesh.shape[data_axis]
        if plan.reps % shards != 0:
            raise ValueError(
                f"reps={plan.reps} must divide over the {shards}-way "
                f"{data_axis!r} mesh axis")
        wire_plane = model_axis in mesh.axis_names
        if wire_plane and plan.d % mesh.shape[model_axis] != 0:
            raise ValueError(
                f"d={plan.d} must divide over the "
                f"{mesh.shape[model_axis]}-way {model_axis!r} mesh axis")
    lams = tuple(s.lam for s in plan.strategies)
    if sparse:
        chols, adj_true, keys = _sparse_plan_setup(*_sparse_setup_key(plan))
        gt_args = (chols,)
    else:
        parents, rhos, adj_true, keys = _plan_setup(*_setup_key(plan))
        gt_args = (parents, rhos)
    stage_fn = _corr_stage if sparse else _weights_stage
    needs_rates = _needs_rates(plan.strategies)
    #: n -> the stacked (S, d) per-feature rate operand of the budget
    #: channels at that sweep point (traced, so it costs no recompiles)
    rates_tail = (
        (lambda n: (_rates_operand(plan.strategies, n, plan.d),))
        if needs_rates else (lambda n: ()))
    faults = plan.faults
    #: per-trial fault keys — rooted apart from the sampler's trial keys
    #: (core.faults._FAULT_ROOT), one independent fault stream per rep
    fkeys = (fault_trial_keys(faults, plan.reps)
             if faults is not None else None)
    lead = () if faults is None else (fkeys,)
    #: (bucket, n) -> (thread, [stage output]) from the cross-bucket
    #: compile-overlap threads; the main loop reuses these results
    prewarmed: dict[tuple[int, int], tuple[threading.Thread, list]] = {}
    path_mode = sparse and plan.path is not None
    if sparse:
        # the glasso solve + support metric stage runs on ONE device even
        # under a mesh (the mesh parallelizes sampling, quantization, Gram
        # and the wire collectives; the statistics are gathered with a
        # device_put — not a host sync — and solved through the same
        # compiled executable as the mesh-less engine, which is what makes
        # mesh metrics bit-identical). Path plans swap in the warm-started
        # fused grid scan + on-device model selection; the corr stages are
        # untouched, so the mesh parity contract carries over unchanged.
        if path_mode:
            metrics_fn = _sparse_path_metrics_fn(
                plan.path, plan.glasso_tol, plan.glasso_steps, chunk)
        else:
            metrics_fn = _sparse_metrics_fn(
                lams, plan.glasso_tol, plan.glasso_steps, chunk)
    warm_thread = None
    if mesh is not None:
        key_data = jax.random.key_data(keys)
        lead_data = (() if faults is None
                     else (jax.random.key_data(fkeys),))
    else:
        if sparse:
            shape_key = (plan.path if path_mode else lams,
                         plan.glasso_tol, plan.glasso_steps,
                         plan.reps, plan.d, chunk)
            dummy = (jnp.zeros((len(lams), plan.reps, plan.d, plan.d),
                               jnp.float32),
                     jnp.zeros((plan.reps, plan.d, plan.d), jnp.bool_))
            if path_mode:
                dummy = dummy + (jnp.asarray(plan.ns[0], jnp.int32),)
        else:
            metrics_fn = _mst_metrics_fn(chunk)
            shape_key = (len(plan.strategies), plan.reps, plan.d, chunk)
            S, r, d, _ = shape_key
            dummy = (jnp.zeros((S, r, d, d), jnp.float32),
                     jnp.zeros((r, d, d), jnp.bool_))
        # overlap the two cold compiles: warm the (sweep-wide, shape-fixed)
        # metric stage (MWST or glasso+support) on a dummy batch in a
        # background thread while the main thread compiles the first
        # bucket's weights/corr stage — XLA releases the GIL, so a cold
        # sweep pays closer to max() than sum() of the two. Only on a
        # genuinely cold shape: warm sweeps must not pay the dummy launch.
        if shape_key not in _warmed_metric_shapes:
            _warmed_metric_shapes.add(shape_key)
            warm_thread = threading.Thread(
                target=lambda fn=metrics_fn, a=dummy: fn(*a), daemon=True)
        # overlap the per-bucket stage compiles across ns: while the main
        # thread compiles (and runs) the first bucket, background threads
        # drive every LATER cold bucket's stage through its own compile,
        # at the first n that bucket serves. The dispatched result is kept
        # (the stage is deterministic), so when the loop reaches that
        # (bucket, n) it joins the thread and REUSES the arrays — the
        # overlap costs no duplicate device work.
        first_n = {}
        for n in plan.ns:
            first_n.setdefault(plan.bucket_for(n), n)
        for b, n0 in list(first_n.items())[1:]:
            stage_key = (plan.strategies, b, engine, plan.structure, faults)
            if stage_key in _warmed_weight_stages:
                continue
            _warmed_weight_stages.add(stage_key)
            out: list = []
            t = threading.Thread(
                target=lambda st=stage_fn(plan.strategies, b, engine,
                                          faults),
                a=(keys, *lead, *gt_args, jnp.asarray(n0, jnp.int32),
                   *rates_tail(n0)),
                o=out: o.append(st(*a)),
                daemon=True)
            t.start()
            prewarmed[(b, n0)] = (t, out)

    point_sums = []
    fault_sums = []
    if warm_thread is not None:
        warm_thread.start()
    for n in plan.ns:
        with span("sweep.point"):
            n_pad = plan.bucket_for(n)
            n_valid = jnp.asarray(n, jnp.int32)
            if mesh is None:
                pre = prewarmed.pop((n_pad, n), None)
                if pre is not None:
                    pre[0].join()
                if pre is not None and pre[1]:
                    out = pre[1][0]
                else:  # not prewarmed (or its thread failed): compute inline
                    out = stage_fn(plan.strategies, n_pad, engine, faults)(
                        keys, *lead, *gt_args, n_valid, *rates_tail(n))
                if faults is None:
                    w = out
                else:
                    w, fsum = out
                    fault_sums.append(fsum)
                if warm_thread is not None:
                    warm_thread.join()
                    warm_thread = None
                point_sums.append(
                    metrics_fn(w, adj_true, n_valid) if path_mode
                    else metrics_fn(w, adj_true))
            elif sparse:
                corr_fn = (
                    _sparse_wire_corr_fn(
                        plan.strategies, n_pad, engine, mesh, data_axis,
                        model_axis, faults)
                    if wire_plane else
                    _sparse_sharded_corr_fn(
                        plan.strategies, n_pad, engine, mesh, data_axis,
                        faults))
                out = corr_fn(key_data, *lead_data, *gt_args, n_valid,
                              *rates_tail(n))
                if faults is None:
                    corr = out
                else:
                    corr, fsum = out
                    fault_sums.append(fsum)
                # gather the rep-sharded statistics onto one device (a d2d
                # copy, NOT a host sync) so the solve+metric executable is the
                # single-device one — bit-identical results by construction
                corr = jax.device_put(corr, jax.devices()[0])
                point_sums.append(
                    metrics_fn(corr, adj_true, n_valid) if path_mode
                    else metrics_fn(corr, adj_true))
            else:
                point_fn = (
                    _wire_point_fn(
                        plan.strategies, n_pad, engine, mesh, data_axis,
                        model_axis, faults, chunk)
                    if wire_plane else
                    _sharded_point_fn(
                        plan.strategies, n_pad, engine, mesh, data_axis,
                        faults, chunk))
                out = point_fn(key_data, *lead_data, *gt_args, adj_true,
                               n_valid, *rates_tail(n))
                if faults is None:
                    point_sums.append(out)
                else:
                    point_sums.append(out[0])
                    fault_sums.append(out[1])
    # (S, len(ns), 3) metric tensor, still on device; THE host sync.
    # host_syncs counts actual read-backs (the += convention every host
    # touch in this loop must follow), so the tests' host_syncs == 1
    # checks stay real canaries — a future per-point device_get sneaking
    # back in shows up as host_syncs > 1. The fault telemetry stacks ride
    # the SAME read-back.
    syncs = 0
    if path_mode:
        # the selected-support sums are the headline channels; the full
        # path's per-lam channel / iteration / selection-histogram / grid
        # sums ride the SAME single read-back as extra leaves
        means = jnp.stack([p[0] for p in point_sums], axis=1) / plan.reps
        extras = tuple(
            jnp.stack([p[i] for p in point_sums], axis=1)
            for i in range(1, 5))
    else:
        means = jnp.stack(point_sums, axis=1) / plan.reps
        extras = None
    bundle = (means, extras)
    with span("sweep.sync"):
        if faults is None:
            m, host_extras = jax.device_get(jax.block_until_ready(bundle))
            fsums = None
        else:
            (m, host_extras), fsums = jax.device_get(jax.block_until_ready(
                (bundle, jnp.stack(fault_sums))))
    syncs += 1

    with span("sweep.report"):
        comm = _comm_reports(plan, engine, data_axis, model_axis, wire_plane,
                             fault_sums=fsums)
        return _package_result(
            plan, m, seconds=time.perf_counter() - t0, host_syncs=syncs,
            comm=comm, mesh_devices=(mesh.size if mesh is not None else 1),
            faults=_fault_stats(plan, fsums),
            tiling={"memory_budget_bytes": plan.effective_memory_budget,
                    "d_tile": engine.d_tile, "n_chunk": engine.n_chunk,
                    "metrics_chunk": chunk},
            path_telemetry=_path_stats(plan, host_extras))


# --------------------------------------------------------------------------
# Single-dataset evaluation (Figs. 10-11: one big x, several strategies)
# --------------------------------------------------------------------------

def learned_adjacency(
    x: jax.Array,
    strategy: Strategy,
    *,
    engine: GramEngine | None = None,
    glasso_tol: float = glasso.SUPPORT_TOL,
    glasso_steps: int = glasso.DEFAULT_STEPS,
) -> jax.Array:
    """Device-side structure estimate for one (n, d) dataset, returning
    the bool adjacency: the sample->quantize->Gram->Boruvka chain for
    tree strategies, or Gram->glasso->partial-correlation support for
    sparse ones (``glasso_tol`` / ``glasso_steps`` mirror the TrialPlan
    knobs, so a sweep point can be reproduced through this door)."""
    from .chow_liu import learn_structure_jit

    if strategy.structure == "sparse":
        corr = estimators.strategy_corr(
            jnp.asarray(x), strategy, engine=resolve_engine(engine))
        theta = glasso.glasso_batch(
            corr[None], strategy.lam, n_steps=glasso_steps)[0]
        return glasso.support_from_theta(theta, glasso_tol)
    return learn_structure_jit(
        jnp.asarray(x), strategy, engine=resolve_engine(engine))


def evaluate_strategies(
    x: jax.Array,
    adj_true: jax.Array,
    strategies: Sequence[Strategy],
    *,
    engine: GramEngine | None = None,
    glasso_tol: float = glasso.SUPPORT_TOL,
    glasso_steps: int = glasso.DEFAULT_STEPS,
) -> dict[str, dict[str, float]]:
    """Score several strategies on ONE dataset against a reference
    adjacency, on device; the per-strategy metric vectors are stacked and
    read back with a SINGLE ``jax.device_get`` for the whole call.

    Returns ``{label: {error, edit_distance, edge_f1}}`` where
    ``edit_distance`` is the edge symmetric difference |E_hat ^ E_ref|
    (host ``tree_edit_distance`` semantics; ``edge_f1`` is the general
    support formula, valid for sparse strategies too — the glasso knobs
    mirror :class:`TrialPlan`'s and only sparse strategies read them).
    """
    x = jnp.asarray(x)
    adj_true = jnp.asarray(adj_true)
    stacked = []
    for strat in strategies:
        est = learned_adjacency(x, strat, engine=engine,
                                glasso_tol=glasso_tol,
                                glasso_steps=glasso_steps)
        stacked.append(jnp.stack([
            trees.structure_error(est, adj_true).astype(jnp.float32),
            trees.structure_hamming(est, adj_true).astype(jnp.float32),
            trees.edge_f1(est, adj_true),
        ]))
    m = jax.device_get(jax.block_until_ready(jnp.stack(stacked)))
    return {
        strat.label: {
            "error": float(m[i, 0]),
            "edit_distance": float(m[i, 1]),
            "edge_f1": float(m[i, 2]),
        }
        for i, strat in enumerate(strategies)
    }


# --------------------------------------------------------------------------
# Scalar Monte-Carlo engines (Figs. 5-6, 8, 9) — vmapped, one sync per call
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _crossover_fn(n: int, reps: int):
    @jax.jit
    def f(key: jax.Array, rho_e: jax.Array, rho_ep: jax.Array) -> jax.Array:
        kk, kj, ks = jax.random.split(key, 3)
        xk = jax.random.normal(kk, (reps, n), jnp.float32)
        xj = rho_e * xk + jnp.sqrt(1 - rho_e**2) * jax.random.normal(
            kj, (reps, n), jnp.float32)
        xs = rho_ep * xk + jnp.sqrt(1 - rho_ep**2) * jax.random.normal(
            ks, (reps, n), jnp.float32)
        th_e = jnp.mean(jnp.sign(xj) * jnp.sign(xk) > 0, axis=1)
        th_ep = jnp.mean(jnp.sign(xk) * jnp.sign(xs) > 0, axis=1)
        return jnp.mean(th_e <= th_ep)

    return f


def mc_sign_crossover(
    n: int, rho_e: float, rho_ep: float, reps: int, seed: int = 0
) -> float:
    """Monte-Carlo Pr(theta_hat_e <= theta_hat_e') for the Fig. 4 shared-
    node pair — the crossover event of Figs. 5-6 — over ``reps`` vmapped
    trials of n samples each (one device sweep, one host sync)."""
    out = _crossover_fn(n, reps)(
        jax.random.key(seed), jnp.float32(rho_e), jnp.float32(rho_ep))
    return float(jax.device_get(jax.block_until_ready(out)))


@functools.lru_cache(maxsize=None)
def _corr_err_fn(n: int, rate: int, reps: int, against_empirical: bool):
    q = PerSymbolQuantizer(rate)

    @jax.jit
    def f(key: jax.Array, rho: jax.Array) -> jax.Array:
        kx, ke = jax.random.split(key)
        x = jax.random.normal(kx, (reps, n), jnp.float32)
        y = rho * x + jnp.sqrt(1 - rho**2) * jax.random.normal(
            ke, (reps, n), jnp.float32)
        est = jnp.mean(q.quantize(x) * q.quantize(y), axis=1)
        ref = jnp.mean(x * y, axis=1) if against_empirical else rho
        return jnp.mean(jnp.abs(ref - est))

    return f


def mc_persymbol_corr_error(
    n: int,
    rho: float,
    rate: int,
    reps: int,
    *,
    against_empirical: bool = False,
    seed: int = 0,
) -> float:
    """Vmapped Monte-Carlo E|ref - mean(x_q * y_q)| for the R-bit
    per-symbol quantizer on a correlated Gaussian pair.

    ``against_empirical=True`` scores against the unquantized empirical
    correlation (the Fig. 8 relative error); False scores against the true
    rho (the Fig. 9 estimation error under a fixed bit budget).
    """
    out = _corr_err_fn(n, rate, reps, against_empirical)(
        jax.random.key(seed), jnp.float32(rho))
    return float(jax.device_get(jax.block_until_ready(out)))
