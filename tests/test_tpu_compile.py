"""Compile the main path's TPU programs for a described v5e, with no chip.

Interpret-mode tests cannot see what Mosaic refuses (unaligned slices,
dynamic lane reads, too much fast memory), so these tests lower and
compile the zone-scan kernels at the chip smoke run's real sizes for a
``v5e:2x2`` topology described by the installed TPU compiler.  Nothing
runs: a pass here is a compile, never a chip result.

The topology is described inside a module fixture — never at import, in a
``skipif`` or in ``parametrize`` — so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import backends, encoding, executor
from repro.kernels.zone_scan import ops

#: the chip smoke run's graphs under the paper defaults: the fused flat
#: stream of the 332,334-edge email-eu-like graph, and its largest zone
SMOKE_SLOTS = 376_832
SMOKE_E_CAP = 72_464
DELTA, L_MAX = 600, 6
BLK = backends.FUSED_BLK_DEFAULT


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of the way
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("with_ts", [False, True])
def test_fused_kernel_compiles_for_v5e(one_chip, with_ts):
    n_blocks = SMOKE_SLOTS // BLK
    fn = jax.jit(functools.partial(
        ops.scan_flat, delta=DELTA, l_max=L_MAX, blk=BLK, interpret=False,
        with_ts=with_ts))
    compiled = fn.lower(*[_i32(one_chip, SMOKE_SLOTS)] * 5,
                        _i32(one_chip, n_blocks),
                        _i32(one_chip, n_blocks)).compile()
    assert _has_kernel(compiled)
    out = compiled.memory_analysis().output_size_in_bytes
    rows = encoding.n_limbs(L_MAX) + 1 + (L_MAX if with_ts else 0)
    assert out >= rows * SMOKE_SLOTS * 4


def test_dense_kernel_compiles_for_v5e(one_chip):
    fn = jax.jit(functools.partial(
        ops.scan_zones, delta=DELTA, l_max=L_MAX, interpret=False))
    zones = (4, SMOKE_E_CAP)
    compiled = fn.lower(
        _i32(one_chip, *zones), _i32(one_chip, *zones), _i32(one_chip, *zones),
        jax.ShapeDtypeStruct(zones, jnp.bool_, sharding=one_chip)).compile()
    assert _has_kernel(compiled)


def test_fused_mine_with_device_fold_compiles_for_v5e(one_chip):
    """The whole fused executable: kernel plus the on-device Phase-2 fold.

    The fold runs at a 4096-row chunk and carry here: above 32k rows the
    TPU sort alone takes tens of seconds to compile, and the chunk size
    does not change what the kernel must lower.
    """
    n_blocks = SMOKE_SLOTS // BLK
    scan = functools.partial(ops.scan_flat, interpret=False)
    compiled = executor._mine_fused_jit.lower(
        *[_i32(one_chip, SMOKE_SLOTS)] * 6, _i32(one_chip, n_blocks),
        _i32(one_chip, n_blocks), delta=DELTA, l_max=L_MAX, scan=scan,
        blk=BLK, fold_chunk=4096, params=((DELTA, L_MAX),),
        merge_caps=(4096,)).compile()
    assert _has_kernel(compiled)
