"""The d1024-structure cell at small sizes on the CPU: the sound program
passes its check; broken underneath, it fails it; the control fails it."""
import cells
from bench import harness
from repro.core import chow_liu, estimators

SMALL = {"d": 32, "n": 512, "datasets": 2}


def test_structure_cell_correct_at_small_size():
    r = cells.run("d1024-structure", SMALL)
    assert r["correct"], r["checks"]
    assert r["metrics"]["structure_s"]["value"] > 0


def test_structure_answer_altered_fails():
    def make(orig):
        def altered(adj):
            edges = orig(adj)
            a, b = edges[0]
            c = next(k for k in range(len(edges) + 1) if k not in (a, b))
            return [(a, c)] + edges[1:]
        return altered

    with cells.patched(chow_liu, "adjacency_to_edges", make):
        assert cells.failed(cells.run("d1024-structure", SMALL))


def test_structure_half_batch_fails():
    def make(orig):
        def half(x, s, **kw):
            return orig(x[: x.shape[0] // 2], s, **kw)
        return half

    with cells.patched(estimators, "strategy_weights", make):
        assert cells.failed(cells.run("d1024-structure", SMALL))


def test_structure_control_fails_the_limit():
    # int16 counts wrap only past 32767 samples: the cell's own n
    _, _, cfg, traffic = harness.cell_spec("d1024-structure")
    cfg = dict(cfg, d=32)
    mod = harness.load_module(harness.HERE + "/entries/learn_structure.py",
                              "e_ls")
    gap = mod.control(cfg, traffic, 5)["edges_differing"]
    assert gap > traffic["limits"]["edges_differing"]
