"""Ahead-of-time compiles of both affinity kernels for a described TPU v5e
chip, at the widths the decision path runs.

Nothing here runs on a device: the TPU compiler, which ships with JAX,
compiles for a chip that is described and not attached, and refuses what
the chip would refuse (unsupported vector ops, more scoped VMEM than a
kernel may use).  Interpret-mode tests cannot see either.  The topology is
described inside a fixture, never at import: only one process may load the
TPU library, and every test worker imports this file.
"""
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.affinity.bulk_kernel import bulk_decide_kernel
from repro.kernels.affinity.kernel import affinity_valid_kernel

# (R, W, T): a wave's distinct-function rows, cluster workers, tag columns
SHAPES = [(128, 16384, 128), (512, 16384, 1024), (128, 65536, 128)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _structs(one_chip, shapes_dtypes):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes_dtypes]


def _assert_kernel_compiled(compiled, name):
    """A Pallas custom call, named for the kernel: the device trace's op
    name, which the benchmark's reduction reads."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text)


def _valid_args(one_chip, R, W, T):
    return _structs(one_chip, [
        ((R, T), jnp.int8),      # aff
        ((R, 1), jnp.float32),   # f_mem
        ((R, 1), jnp.float32),   # cap_pct
        ((R, 1), jnp.int32),     # max_conc
        ((W, T), jnp.int32),     # occ
        ((1, W), jnp.float32),   # mem_used
        ((1, W), jnp.float32),   # max_mem
        ((1, W), jnp.int32),     # n_funcs
        ((R, W), jnp.int8),      # wmask
    ])


@pytest.mark.parametrize("R,W,T", SHAPES)
def test_affinity_valid_kernel_compiles_for_v5e(one_chip, R, W, T):
    args = _valid_args(one_chip, R, W, T)
    _assert_kernel_compiled(affinity_valid_kernel.lower(*args).compile(),
                            "affinity_valid_kernel")


def test_kernel_name_outlives_a_renamed_wrapper(one_chip):
    """The op keeps the kernel's own name under a jitted wrapper of
    another name."""
    def renamed(*args):
        return affinity_valid_kernel.__wrapped__(*args)

    args = _valid_args(one_chip, *SHAPES[0])
    _assert_kernel_compiled(jax.jit(renamed).lower(*args).compile(),
                            "affinity_valid_kernel")


@pytest.mark.parametrize("R,W,T", SHAPES)
def test_bulk_decide_kernel_compiles_for_v5e(one_chip, R, W, T):
    args = _structs(one_chip, [
        ((R, T), jnp.int8),      # aff
        ((R, 1), jnp.float32),   # f_mem
        ((R, 1), jnp.float32),   # cap_pct
        ((R, 1), jnp.int32),     # max_conc
        ((R, 1), jnp.int32),     # strat
        ((W, T), jnp.int32),     # occ
        ((1, W), jnp.float32),   # mem_used
        ((1, W), jnp.float32),   # max_mem
        ((1, W), jnp.int32),     # n_funcs
        ((R, W), jnp.int8),      # wmask
        ((R, W), jnp.int32),     # warm
    ])
    _assert_kernel_compiled(bulk_decide_kernel.lower(*args).compile(),
                            "bulk_decide_kernel")
