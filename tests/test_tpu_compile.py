"""Compile every Pallas kernel for a described TPU v5e chip (no chip needed).

The TPU compiler ships with jaxlib's TPU plugin and compiles for a chip
that is described rather than attached, so these tests catch what
interpret mode cannot: block shapes the Mosaic lowering refuses, layouts
it cannot relayout, VMEM overruns, and query-eval programs that would
not fit the chip's 16 GB of HBM.  Shapes are those of the one-chip smoke
deployment (`chip_smoke.py`): 1,024 partitions × 16,384 rows of the
TPC-H-like table.

The topology is described inside a module fixture (never at import), the
kernels' `interpret()` switch is steered per test with `monkeypatch`, and
the persistent compilation cache is off for the module: an entry written
for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused, groupagg, histogram, moments, pdist, predicate, tree_hist

P, R = 1024, 16384  # the smoke deployment's partitions × rows
STACK_COLS = 17  # 16 TPC-H-like columns + the ones column
HBM_BYTES = 16 * 10**9  # one v5e chip

KERNEL_MODULES = (fused, groupagg, histogram, moments, pdist, predicate, tree_hist)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # no CPU trace of a kernel may stand in for a TPU one
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def native(monkeypatch, one_chip):
    """Lower the Pallas kernels natively (as on a chip), not interpreted."""
    for mod in KERNEL_MODULES:
        monkeypatch.setattr(mod, "interpret", lambda: False)
    return one_chip


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, static, sharding, *shapes):
    """Fresh jit of the un-jitted kernel body, compiled for the chip."""
    body = getattr(fn, "__wrapped__", fn)
    args = [_spec(sharding, *s) for s in shapes]
    return jax.jit(body, static_argnames=tuple(static)).lower(*args, **static).compile()


I32 = jnp.int32
CASES = {
    "moments": (moments.moments, {}, [((P, R),)]),
    "histogram_range": (histogram.histogram_range, {}, [((P, R),), ((P, 11),)]),
    "bincount_wide": (histogram.bincount, {"card": 2526}, [((P, R), I32)]),
    "bincount": (histogram.bincount, {"card": 200}, [((P, R), I32)]),
    "fused_eval": (
        fused.fused_eval, {"num_groups": 128},
        [((P, 8, R),), ((P, 8),), ((P, 8),), ((P, 8, 4),), ((P, 4, R),), ((P, R), I32)],
    ),
    "group_aggregate": (
        groupagg.group_aggregate, {"num_groups": 2048},
        [((P, 4, R),), ((P, R),), ((P, R), I32)],
    ),
    "tree_hist": (
        tree_hist.tree_hist, {"num_nodes": 8, "num_feats": 96},
        [((61440, 12), I32), ((12,), I32), ((61440,), I32), ((61440,),), ((61440,),)],
    ),
    "predicate_eval": (
        predicate.predicate_eval, {"num_groups": 4},
        [((P, 8, R),), ((P, 8),), ((P, 8),), ((P, 8, 4),)],
    ),
    "pdist": (pdist.pdist_sq, {}, [((P, 64),), ((128, 64),)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(native, name):
    fn, static, shapes = CASES[name]
    compiled = _compile(fn, static, native, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_stacked_eval_fits_one_chip(native):
    """The stacked query-eval program over the full device column stack
    (Pallas fused kernel) compiles and fits the chip's HBM."""
    from repro.queries import device

    qb, cb, gb, vb, radix = 1, 8, 4, 4, 128
    compiled = _compile(
        device._eval_core, {"num_groups": gb, "radix": radix, "use_ref": False}, native,
        ((STACK_COLS, P, R),), ((qb, cb), I32), ((qb, cb),), ((qb, cb),),
        ((qb, cb, gb),), ((qb, vb, STACK_COLS),), ((qb, STACK_COLS),),
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"stacked eval needs {used / 1e9:.2f} GB"
