"""The pallas fabric engine's program compiles for a described TPU v5e.

Interpret-mode tests run the kernels through XLA on the CPU; they cannot
see what Mosaic refuses (memory spaces, gathers, unaligned blocks, VMEM
over-use).  These tests hand the TPU compiler the engine's jitted
program — XLA gathers and finish reductions around the queue-scan
kernels — at the ``weak_scaling_xl`` (16^3 ranks) and
``weak_scaling_xxl`` (32^3 ranks) smoke shapes, in the grid path's
``finish`` mode and the warm driver's ``arrivals`` mode, with the
kernels compiled (``interpret=False``) and float32, as on the chip.

Nothing runs: a compile that passes is not a chip run.  The topology is
described inside a module fixture, never at import, so every pytest
worker collects the same tests and only the worker given this file loads
the TPU compiler library.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from _engines import grid_items  # noqa: E402
from repro.core import fabric_pallas as fp  # noqa: E402

SHAPES = {"weak_scaling_xl": (16, 16, 16), "weak_scaling_xxl": (32, 32, 32)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@functools.lru_cache(maxsize=None)
def _points(dims):
    """The spec's smoke points (``pt2pt_single`` and ``part``) as grid
    items with their finish specs."""
    return grid_items([dict(approach=ap, dims=dims, theta=4, n_threads=2,
                            n_vcis=2, local_shape=(64, 64, 64),
                            bytes_per_cell=8.0)
                       for ap in ("pt2pt_single", "part")])


def _operands(mode, dims):
    """``(core, float operand shapes, static operand arrays)`` of one
    program build, assembled on the host exactly as the engine does."""
    items, fins = _points(dims)
    if mode == "finish":
        core, dyn, statics, _ = fp._assemble(items, fins)
        return core, [np.shape(a) for a in dyn], statics
    it = items[1]  # the warm driver path advances one batch at a time
    lays = fp._raw_layouts(it.src, it.dst, it.vci % it.n_vcis, it.n_vcis,
                           it.n_ranks, it.key)
    core, statics, _ = fp._arr_structure(lays, len(it))
    n = len(it)
    return core, [(n,)] * 4 + [(len(lay[2]),) for lay in lays], statics


@pytest.mark.parametrize("mode", ["finish", "arrivals"])
@pytest.mark.parametrize("spec", sorted(SHAPES))
def test_fabric_program_compiles_for_v5e(spec, mode, one_chip,
                                         no_compile_cache):
    core, dyn_shapes, statics = _operands(mode, SHAPES[spec])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = ([sds((12,), jnp.float32)]
            + [sds(s, jnp.float32) for s in dyn_shapes]
            + [sds(np.shape(a), a.dtype) for a in statics])
    meta = fp._Meta(mode=mode, f64=False, interpret=False, **core)
    compiled = fp._build_call(meta).lower(*args).compile()
    n_buckets = len(core["st1"]) + len(core["st2"]) + len(core["st3"])
    assert compiled.as_text().count("tpu_custom_call") >= n_buckets
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
