"""The XLA module names the stage readers match are the names the program's
stages lower to: a rename would turn those metrics to nothing in silence.
(The kernels' instruction names are pinned where they are compiled for a
described v5e, in ``tests/test_tpu_compile.py``.)"""
import os
import re

import jax.numpy as jnp
import pytest

from bench import harness
from repro.core import Strategy, TrialPlan, chow_liu, experiments
from repro.core.gram import resolve_engine

PLAN = TrialPlan(d=8, ns=(64,), reps=4,
                 strategies=(Strategy("sign"), Strategy("persymbol", rate=2)))


def _weights_stage():
    parents, rhos, _, keys = experiments._plan_setup(
        *experiments._setup_key(PLAN))
    stage = experiments._weights_stage(PLAN.strategies, 64,
                                       resolve_engine(None), None)
    return stage.lower(keys, parents, rhos, jnp.asarray(64, jnp.int32))


def _metrics_stage():
    S, r, d = len(PLAN.strategies), PLAN.reps, PLAN.d
    return experiments._mst_metrics_fn(None).lower(
        jnp.zeros((S, r, d, d), jnp.float32), jnp.zeros((r, d, d), bool))


def _boruvka():
    return chow_liu.boruvka_mst.lower(jnp.zeros((8, 8), jnp.float32))


@pytest.mark.parametrize("metric,lower,module", [
    ("weights_stage_ms.trials", _weights_stage, "jit_f"),
    ("mst_stage_ms.trials", _metrics_stage, "jit__lambda"),
    ("mst_ms.structure", _boruvka, "jit_boruvka_mst"),
])
def test_stage_readers_match_the_lowered_module_names(metric, lower, module):
    name = re.search(r"^module @(\S+)", lower().as_text(), re.M).group(1)
    assert name == module
    reader = harness.load_module(
        os.path.join(harness.HERE, "metrics", metric + ".py"), "r_" + module)
    assert re.search(reader.MODULE, name) and re.search(reader.MODULE,
                                                        name + "(12)")
