"""Window loop: a closed loop of whole ``run_trials`` sweeps.

Each sweep is the configuration's ``TrialPlan`` with a fresh ``seed0``
derived from the run's seed and the sweep's index, so every sweep pays its
own host tree draws, as a researcher's sweep does.

``trials_per_s`` is the trials of the sweeps completed in the window over
the window's length; the window ends at a sweep boundary. The check
re-runs ``check_sweeps`` of the window's sweeps, drawn from the seed,
through the plain reference and compares the per-point error and edit
counts (``checks``: the gaps summed over the sweep's points, the largest
over the sweeps compared).
"""
from __future__ import annotations

import time

import numpy as np

from bench import seeds
from bench.reference import tree as ref


def _strategy(spec: dict):
    from repro.core import Strategy

    return Strategy(spec["method"], rate=spec.get("rate", 1),
                    wire=spec.get("wire", "int8"), mst=spec.get("mst", "boruvka"))


def plan_fields(cfg: dict) -> dict:
    """The sweep's plan, from the configuration's sizes."""
    return {"d": cfg["d"], "ns": tuple(cfg["ns"]), "reps": cfg["reps"],
            "rho_min": cfg["rho_min"], "rho_max": cfg["rho_max"],
            "strategies": [dict(s) for s in cfg["strategies"]]}


class Cell:
    def __init__(self, cfg, traffic, seed, devices, spans):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices, self.span = devices, spans
        self.fields = plan_fields(cfg)
        self.done: list[tuple[int, dict, dict]] = []  # (seed0, err, edit)
        self.counters: dict = {}

    def _plan(self, seed0: int):
        from repro.core import TrialPlan

        f = self.fields
        return TrialPlan(d=f["d"], ns=f["ns"], reps=f["reps"],
                         rho_min=f["rho_min"], rho_max=f["rho_max"],
                         strategies=tuple(map(_strategy, f["strategies"])),
                         seed0=seed0)

    def setup(self):
        from repro.core import run_trials

        self.run_trials = run_trials
        # warm every stage this sweep compiles, on a seed the window never uses
        with self.span("warmup"):
            self.run_trials(self._plan(seeds.derive(self.seed, 1 << 31)))

    def window(self, seconds: float) -> dict:
        trials = 0
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            seed0 = seeds.derive(self.seed, i)
            plan = self._plan(seed0)
            with self.span("run_trials"):
                res = self.run_trials(plan)
            self.done.append((seed0, res.error_rate, res.edit_distance))
            trials += plan.trials
            i += 1
        elapsed = time.perf_counter() - t0
        self.counters = {"sweeps": i, "trials": trials, "elapsed_s": elapsed,
                         "reps": self.fields["reps"]}
        return {"metrics": {"trials_per_s": trials / elapsed},
                "counters": self.counters, "attempted": i, "failed": 0}

    def release(self):
        import jax
        from repro.core.experiments import clear_compile_caches

        self.run_trials = None
        clear_compile_caches()
        jax.clear_caches()

    def check(self) -> list[tuple[str, float, float]]:
        k = min(self.traffic["check_sweeps"], len(self.done))
        pick = seeds.rng(self.seed, 2).choice(len(self.done), k, replace=False)
        gaps = np.zeros((2,), np.int64)
        for idx in sorted(pick):
            seed0, err, edit = self.done[idx]
            got = program_counts(self.fields, err, edit)
            want = reference_counts(self.fields, seed0)
            gaps = np.maximum(gaps, gap_sums(got, want))
        lim = self.traffic["limits"]
        return [("error_gap_sum", float(gaps[0]), lim["error_gap_sum"]),
                ("edit_gap_sum", float(gaps[1]), lim["edit_gap_sum"])]


def labels(fields: dict) -> list[str]:
    out = []
    for s in fields["strategies"]:
        m = s["method"]
        out.append("sign" if m == "sign" else
                   "original" if m == "original" else f"R{s.get('rate', 1)}")
    return out


def program_counts(fields: dict, err: dict, edit: dict) -> np.ndarray:
    """(S, len(ns), 2) trial counts from a TrialResult's per-point means."""
    reps = fields["reps"]
    return np.rint(np.array(
        [[err[lab], edit[lab]] for lab in labels(fields)], np.float64
    ).transpose(0, 2, 1) * reps).astype(np.int64)


def gap_sums(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """[error, edit] count gaps summed over every (strategy, n) point."""
    return np.abs(got[..., :2] - want[..., :2]).sum(axis=(0, 1))


def reference_counts(fields: dict, seed0: int, **kw) -> np.ndarray:
    pairs = [(s["method"], s.get("rate", 1)) for s in fields["strategies"]]
    return ref.sweep(fields["d"], fields["ns"], pairs, fields["reps"],
                     fields["rho_min"], fields["rho_max"], seed0, **kw)


def control(cfg: dict, traffic: dict, seed: int) -> dict:
    """The control's readings on one sweep drawn from ``seed``: the plain
    reference in the program's place, with the steps the traffic's
    ``control`` names under ``lower`` computed one precision below what the
    configuration states (``reference.tree.LOWER``), compared as the check
    compares the program."""
    fields = plan_fields(cfg)
    seed0 = seeds.derive(seed, 0)
    want = reference_counts(fields, seed0)
    got = reference_counts(fields, seed0, lower=tuple(traffic["control"]["lower"]))
    gap = gap_sums(got, want)
    return {"error_gap_sum": int(gap[0]), "edit_gap_sum": int(gap[1])}
