"""Fig. 3: structure estimation error vs n for R in {sign,1,2,3,4,inf}.

Random 20-node GGMs; per (method, n) the error rate over ``reps`` trials.
Paper claims: sign > 1-bit per-symbol; 4-bit per-symbol ~ original.

Runs on the vmapped trial engine (``repro.core.experiments.run_trials``):
the whole (6 methods x ns x reps) sweep is a handful of compiled device
calls with one host sync per sweep point.
"""
from __future__ import annotations

from repro.core.experiments import TrialPlan, run_trials
from repro.core.strategy import FIG3_STRATEGIES

from .common import save_artifact

D = 20
NS = (125, 250, 500, 1000, 2000, 4000)


def run(reps: int = 120, quick: bool = False) -> dict:
    ns = NS[:4] if quick else NS
    reps = 30 if quick else reps
    plan = TrialPlan(d=D, ns=ns, strategies=FIG3_STRATEGIES, reps=reps)
    res = run_trials(plan)
    table = res.error_rate
    for key, errs in table.items():
        print(f"fig3 {key:<9} " + " ".join(f"{e:.3f}" for e in errs),
              flush=True)
    payload = {"d": D, "ns": list(ns), "reps": reps, "error": table,
               "edit_distance": res.edit_distance}
    # paper-claim checks (soft, recorded in the artifact):
    checks = {
        "sign_beats_ps1": all(
            s <= p + 0.08 for s, p in zip(table["sign"], table["R1"])
        ),
        # one-sided: the paper's claim is that 4 bits suffice — R4 must not
        # be materially WORSE than the unquantized baseline (beating it at
        # small n is fine: eq. 30's unbiased rho^2 can out-rank the plain
        # squared sample correlation there, and quick runs are 30-rep MC)
        "ps4_close_to_original": all(
            a - b <= 0.12 for a, b in zip(table["R4"], table["original"])
        ),
        "errors_decay": table["sign"][-1] <= table["sign"][0],
    }
    payload["checks"] = checks
    save_artifact("fig3_structure_error", payload)
    return payload


if __name__ == "__main__":
    run()
