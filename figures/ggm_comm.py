"""Distributed-GGM communication cost (the paper's own cost table).

For the production GGM config (d features over the model axis) this
reports the bits crossing the links per method/rate — the quantity the
paper optimizes (n*d*R) — against the float baseline (n*d*64: the paper's
experiments store doubles), plus a live multi-device run on the host mesh
verifying the pipeline end-to-end where device count allows.
"""
from __future__ import annotations

import numpy as np
import jax

from repro.core.distributed import communication_bits
from .common import save_artifact


def run(quick: bool = False) -> dict:
    d, n = (64, 4096) if quick else (4096, 1 << 20)
    rows = []
    base_bits = communication_bits(n, d, 64)  # float64 baseline (paper §6)
    for method, rate in [("sign", 1), ("persymbol", 2), ("persymbol", 4),
                         ("persymbol", 8)]:
        bits = communication_bits(n, d, rate)
        rows.append({
            "method": method, "rate": rate,
            "bits_total": bits,
            "compression_vs_f64": base_bits / bits,
            "MiB_on_wire": bits / 8 / 2**20,
        })
        print(f"ggm_comm {method} R={rate}: {bits/8/2**20:.1f} MiB "
              f"({base_bits/bits:.0f}x smaller than f64)", flush=True)

    live = None
    if len(jax.devices()) >= 2:
        import repro.core as core
        from repro.core.distributed import distributed_learn_structure
        rng = np.random.default_rng(0)
        dd, nn = 16, 4096
        edges = core.random_tree(dd, rng)
        w = rng.uniform(0.4, 0.9, dd - 1)
        x = core.sampler.sample_tree_ggm(jax.random.key(0), nn, dd, edges, w)
        mesh = jax.make_mesh(
            (1, len(jax.devices())), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        est = distributed_learn_structure(x, mesh, method="sign")
        live = {"devices": len(jax.devices()),
                "edit_distance": core.tree_edit_distance(edges, est)}
        print(f"ggm_comm live run on {live['devices']} devices: "
              f"edit_distance={live['edit_distance']}", flush=True)

    payload = {"d": d, "n": n, "rows": rows, "live": live}
    save_artifact("ggm_comm", payload)
    return payload


if __name__ == "__main__":
    run()
