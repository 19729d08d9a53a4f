"""The fig3-sweep cell at a small size on the CPU: the sound program
passes its check; broken underneath, it fails it; the control fails it."""
import jax.numpy as jnp
import numpy as np

import cells
from bench import harness
from repro.core import estimators, experiments

SMALL = {"reps": 64, "ns": [500, 2000]}


def test_sweep_cell_correct_at_small_size():
    r = cells.run("fig3-sweep", SMALL)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["metrics"]["trials_per_s"]["value"] > 0
    assert set(r["checks"]) == {"error_gap_sum", "edit_gap_sum"}
    assert list(r)[-1] == "checks"


def test_sweep_answer_altered_fails():
    def make(orig):
        def altered(w, chunk=None):
            est = orig(w, chunk)
            d = est.shape[-1]
            return est.at[0].set(~est[0] & ~jnp.eye(d, dtype=bool))
        return altered

    with cells.patched(experiments, "boruvka_mst_batch", make):
        assert cells.failed(cells.run("fig3-sweep", SMALL))


def test_sweep_half_batch_fails():
    def make(orig):
        def half(x, s, n_valid=None, **kw):
            m = x.shape[-2] // 2
            return orig(x[:, :m], s, n_valid=jnp.minimum(n_valid, m), **kw)
        return half

    with cells.patched(estimators, "strategy_weights_batch", make):
        assert cells.failed(cells.run("fig3-sweep", SMALL))


def test_sweep_control_fails_the_limits():
    _, _, cfg, traffic = harness.cell_spec("fig3-sweep")
    cfg = dict(cfg, ns=[125, 250], strategies=cfg["strategies"][2:])
    mod = harness.load_module(harness.HERE + "/entries/run_trials.py", "e_rt")
    out = mod.control(cfg, traffic, 5)
    err, edit = out["error_gap_sum"], out["edit_gap_sum"]
    lim = traffic["limits"]
    assert err > lim["error_gap_sum"] or edit > lim["edit_gap_sum"], out
