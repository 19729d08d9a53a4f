"""Paper-figure driver: one module per figure of arXiv 1809.08067, plus the
glasso extension and the distributed communication count.

  PYTHONPATH=src python -m figures.run            # full (slow)
  PYTHONPATH=src python -m figures.run --quick    # small reps
  PYTHONPATH=src python -m figures.run --only fig3,fig7

Each module writes ``figures/artifacts/<name>.json`` and returns its
paper-claim ``checks``; the driver exits 1 when any check fails. These
measure accuracy and bits, not speed: speed is measured on the chip by
``bench/`` (see PERF.md).
"""
from __future__ import annotations

import argparse
import time

from . import (ext_glasso, fig3_structure_error, fig56_crossover, fig7_star,
               fig8_rel_error, fig9_quality_quantity, fig1011_skeleton,
               ggm_comm)

FIGURES = {
    "fig3": fig3_structure_error.run,
    "fig56": fig56_crossover.run,
    "fig7": fig7_star.run,
    "fig8": fig8_rel_error.run,
    "fig9": fig9_quality_quantity.run,
    "fig1011": fig1011_skeleton.run,
    "ggm_comm": ggm_comm.run,
    "ext_glasso": ext_glasso.run,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    names = [n for n in args.only.split(",") if n] or list(FIGURES)

    failures = []
    for name in names:
        print(f"\n=== {name} " + "=" * (68 - len(name)), flush=True)
        t0 = time.time()
        try:
            result = FIGURES[name](quick=args.quick)
            checks = (result or {}).get("checks", {})
            bad = [k for k, v in checks.items() if not v]
            status = "PASS" if not bad else f"CHECKS-FAILED:{bad}"
            if bad:
                failures.append((name, bad))
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            status = f"ERROR: {e}"
        print(f"=== {name} [{status}] ({time.time()-t0:.1f}s)", flush=True)

    print("\n" + "=" * 72)
    if failures:
        print(f"{len(failures)} figure(s) with failed checks/errors:")
        for f in failures:
            print("  ", f)
        return 1
    print("all figures passed their paper-claim checks")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
