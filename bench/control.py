"""Read a cell's control on several seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

The control is the plain reference put in the program's place, computed
one precision step below what its configuration states; each traffic
file's ``control`` says which steps. It prints, per seed, the numbers the
check compares. The benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--chips", type=int, default=None,
                    help="chips to hold (default: the cell's); the control "
                         "is the reference, which runs on one")
    args = ap.parse_args()
    bench, wl, cfg, traffic = harness.cell_spec(args.workload)
    harness.setup_jax(args.chips or int(wl["chips"]), require_chip=True)
    mod = harness.load_module(
        os.path.join(harness.HERE, "entries", traffic["entry"] + ".py"),
        "bench_entry_" + traffic["entry"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = mod.control(cfg, traffic, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": out,
                          "seconds": time.perf_counter() - t0}), flush=True)
