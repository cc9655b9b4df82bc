"""Compile rehearsals: the four main-path gang kernels, compiled by the TPU
compiler for a described (not attached) v5e at the shapes ``chip_smoke.py``
drives — 16 shards x f=3 witnesses (64 gang lanes) x 1024 sets x 4 ways,
batches of 1024, two-key groups, 1024-entry master-window rings.

Interpret mode cannot see what Mosaic refuses (unsupported primitives,
unaligned slices, VMEM over-use); these tests can, with no chip attached.
The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests.
"""
from __future__ import annotations

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core.fastbatch import RING_CAP  # noqa: E402
from repro.kernels import gang_rows  # noqa: E402
from repro.kernels.ops import (  # noqa: E402
    DEFAULT_N_SLOTS,
    _gang_fastpath_impl,
    _gang_gc_impl,
    _gang_groups_impl,
    _gang_record_impl,
)

N_SHARDS, F = 16, 3
LANES = 64                      # n_shards * f = 48, rounded up to a pow2
N_SETS, N_WAYS = 1024, 4        # paper §B.1 witness geometry
B, K = 1024, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: a ShapeDtypeStruct placed on one v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _table(spec):
    from repro.kernels import GangTable

    R = gang_rows(LANES * N_SETS)
    u, i = jnp.uint32, jnp.int32
    return GangTable(*(spec((N_WAYS, R), dt) for dt in (u, u, i, u, u, i)))


def _args(spec, name):
    u, i = jnp.uint32, jnp.int32
    common = dict(n_sets=N_SETS, interpret=False)
    if name == "gang_record":
        return _gang_record_impl, (
            _table(spec), spec((B,), u), spec((B,), u), spec((B,), i),
            spec((B,), i), spec((B,), i), spec((B,), u), spec((B,), u),
        ), common
    if name == "gang_record_groups":
        return _gang_groups_impl, (
            _table(spec), spec((B, K), u), spec((B, K), u), spec((B, K), i),
            spec((B, K), i), spec((B,), i), spec((B,), u), spec((B,), u),
            spec((B,), i),
        ), common
    if name == "gang_gc":
        return _gang_gc_impl, (
            _table(spec), spec((B,), u), spec((B,), u), spec((B,), u),
            spec((B,), u), spec((B,), i), spec((B,), i), spec((LANES,), i),
        ), dict(common, do_age=True)
    assert name == "gang_fastpath_batch"
    ring = (N_SHARDS, RING_CAP)
    return _gang_fastpath_impl, (
        _table(spec), spec((B,), u), spec((B,), u), spec((B,), i),
        spec((B,), i), spec((B,), u), spec((B,), u), spec((B,), i),
        spec((DEFAULT_N_SLOTS,), i), spec((N_SHARDS, F), i),
        spec(ring, u), spec(ring, u), spec(ring, i),
        spec((N_SHARDS,), i), spec((N_SHARDS,), i),
    ), dict(common, n_slots=DEFAULT_N_SLOTS, f=F)


@pytest.mark.parametrize("name", ["gang_record", "gang_record_groups",
                                  "gang_gc", "gang_fastpath_batch"])
def test_main_path_kernel_compiles_for_v5e(name, spec, no_persistent_cache,
                                           record_property):
    impl, args, static = _args(spec, name)
    compiled = impl.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"
    mem = compiled.memory_analysis()
    record_property("memory_analysis", str(mem))
    print(f"{name}: {mem}")
    # The six [4, 65536] planes are 6 MiB; arguments must not be padded
    # towards the 128-lane [R, 4] layout (which would be ~32x that).
    plane_bytes = 6 * N_WAYS * gang_rows(LANES * N_SETS) * 4
    assert mem.argument_size_in_bytes < 2 * plane_bytes


# The largest work-item counts that fit v5e's SMEM (see check_smem): one
# more item must be refused before the compile, and the limit itself must
# compile.
SMEM_LIMITS = {"record_k1": 31744, "record_k2": 21504, "gc": 43008}


def _smem_call(spec, name, G):
    from repro.kernels.witness_record import gang_gc_pallas, gang_record_pallas

    u, i = jnp.uint32, jnp.int32
    if name == "gc":
        return gang_gc_pallas.lower(
            _table(spec), *(spec((G,), dt) for dt in (u, u, u, u, i, i)),
            spec((gang_rows(LANES * N_SETS),), i),
            n_sets=N_SETS, interpret=False)
    k = 1 if name == "record_k1" else 2
    return gang_record_pallas.lower(
        _table(spec), *(spec((G, k), dt) for dt in (u, u, i, i, i)),
        spec((G,), u), spec((G,), u), spec((G,), i),
        n_sets=N_SETS, interpret=False)


@pytest.mark.parametrize("name", sorted(SMEM_LIMITS))
def test_smem_limit_matches_v5e_compile(name, spec, no_persistent_cache):
    G = SMEM_LIMITS[name]
    with pytest.raises(ValueError, match="SMEM"):
        _smem_call(spec, name, G + 1)
    compiled = _smem_call(spec, name, G).compile()
    assert "tpu_custom_call" in compiled.as_text()
