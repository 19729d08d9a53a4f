"""Compile-only checks of the Gram kernels and the MST stage for a
described TPU v5e.

Each case lowers and compiles one kernel or solver at a real width for one
chip of a ``v5e:2x2`` topology that is described, not attached (or, for
the serving fold, over all four chips), and asserts that the kernel is in
the compiled program (``tpu_custom_call``), or that the solver compiled
with no scatter, gather or sort. Nothing runs, so these say
nothing about results or speed; they catch what the chip's compiler
refuses and interpret mode accepts (unaligned blocks, too much VMEM,
unsupported vector types, kernels left to automatic partitioning).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import GramEngine, Strategy
from repro.core.chow_liu import boruvka_mst, boruvka_mst_batch
from repro.core.distributed import build_weights_fn
from repro.kernels.sign_corr import code_corr, sign_corr, sign_corr_packed
from repro.serve import table


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile_text(fn, one_chip, *operands) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(text: str) -> set[str]:
    """Instruction names of the compiled program's Mosaic kernels, numeric
    suffix dropped: the op names a device trace shows for them."""
    return {re.sub(r"\.\d+$", "",
                   ln.split(" = ", 1)[0].split()[-1].lstrip("%"))
            for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln}


@pytest.mark.parametrize("shape", [(60, 4096, 20), (65536, 1024)])
def test_sign_corr_compiles_for_v5e(one_chip, shape):
    text = _compile_text(sign_corr, one_chip, (shape, jnp.int8))
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"sign_corr"}


@pytest.mark.parametrize("shape", [(60, 4096, 20), (8192, 1024)])
def test_code_corr_compiles_for_v5e(one_chip, shape):
    text = _compile_text(code_corr, one_chip, (shape, jnp.int8),
                         ((16,), jnp.float32))
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"code_corr"}


@pytest.mark.parametrize("shape,n", [((60, 20, 512), 4096),
                                     ((4096, 8192), 65536),
                                     ((1024, 8192), 65536)])
def test_sign_corr_packed_compiles_for_v5e(one_chip, shape, n):
    # (1024, 8192): the structure cells' payload at the default tiles
    # compiled inside another jitted function: the name stays the kernel's
    text = _compile_text(lambda p: sign_corr_packed(p, n), one_chip,
                         (shape, jnp.uint8))
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"sign_corr_packed"}


@pytest.mark.parametrize("fn,shape", [
    (boruvka_mst, (1024, 1024)),
    (jax.vmap(boruvka_mst), (6000, 20, 20)),
    (boruvka_mst_batch, (6000, 20, 20)),
    (functools.partial(boruvka_mst, edges=True), (1024, 1024)),
], ids=["single_d1024", "vmap", "batch", "single_d1024_edges"])
def test_boruvka_compiles_for_v5e(one_chip, fn, shape):
    """The structure cells' d=1024 solve, as adjacency and as the edge
    record they read back, and the sweep's stack of 6000 d=20 trials,
    vmapped and laid along lanes."""
    text = _compile_text(fn, one_chip, (shape, jnp.float32))
    ops = set(re.findall(r"\s([a-z][\w-]*)\(%", text))
    assert "while" in ops
    assert not ops & {"scatter", "gather", "sort"}


@pytest.mark.parametrize("kind", ["codes", "packed"])
def test_tenant_sharded_fold_compiles_for_v5e(v5e, kind):
    """The serving plane's fold over a 4-chip tenant mesh: a Mosaic kernel
    cannot be partitioned automatically, so each chip must contract its
    own slots."""
    mesh = Mesh(np.array(v5e.devices), ("tenant",))
    eng = GramEngine(backend="pallas", interpret=False)
    slots, block_n, d = 64, 48, 32
    sharded = NamedSharding(mesh, PartitionSpec("tenant"))
    if kind == "codes":
        stage = table._codes_fold_stage(slots, block_n, d, "sign", 1, eng,
                                        mesh)
        args = [jax.ShapeDtypeStruct((slots, block_n, d), jnp.int8,
                                     sharding=sharded)]
    else:
        stage = table._packed_fold_stage(slots, block_n, d, eng, mesh)
        args = [jax.ShapeDtypeStruct((slots, d, block_n // 8), jnp.uint8,
                                     sharding=sharded),
                jax.ShapeDtypeStruct((slots,), jnp.int32,
                                     sharding=NamedSharding(mesh,
                                                            PartitionSpec()))]
    assert "tpu_custom_call" in stage.lower(*args).compile().as_text()


@pytest.mark.parametrize("placement", ["replicated", "rowblock"])
def test_wire_runtime_compiles_for_v5e(v5e, placement):
    """The four-machine deployment's runtime: (65536, 1024) samples whose
    features lie over a (1, 4) mesh, each chip's packed sign payload
    gathered over the mesh, the Pallas packed Gram inside the shard_map.
    The jitted runtime keeps its module name."""
    mesh = Mesh(np.array(v5e.devices).reshape(1, 4), ("data", "model"))
    fn, sharding = build_weights_fn(
        mesh, strategy=Strategy("sign", wire="packed", placement=placement),
        engine=GramEngine(backend="pallas", interpret=False))
    lowered = fn.lower(jax.ShapeDtypeStruct((65536, 1024), jnp.float32,
                                            sharding=sharding))
    assert re.search(r"^module @(\S+)", lowered.as_text(),
                     re.M).group(1) == "jit_local_weights"
    text = lowered.compile().as_text()
    assert _kernel_names(text) == {"sign_corr_packed"}
    assert re.search(r"\ball-gather(-start)?\(", text)
