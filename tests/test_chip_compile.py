"""Every Pallas kernel compiles with the TPU compiler at real widths.

Interpret mode (tests/test_kernels.py) checks the numerics on the CPU but
accepts layouts Mosaic refuses: unaligned slices, tiles below a dtype's
native (sublane, lane) shape, VMEM over budget.  These tests compile each
kernel for a described (not attached) v5e chip, so a refusal fails here.
Nothing runs, so they say nothing about results or time.

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, and the test workers all import this
file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as fops
from repro.kernels.local_reduce import ops as lops
from repro.kernels.quantize import ops as qops

BUCKET_BYTES = 32 << 20          # one gradient bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache (there is no device), so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *structs):
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("seq", [2048, 8192])
def test_flash_attention_compiles(one_chip, seq):
    # bf16, head_dim 128, GQA group 8 (64 query heads over 8 kv heads)
    q = jax.ShapeDtypeStruct((1, seq, 64, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    _compile(functools.partial(fops.attention, causal=True,
                               force_kernel=True), q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_local_reduce_compiles(one_chip, dtype):
    n = BUCKET_BYTES // jnp.dtype(dtype).itemsize
    x = jax.ShapeDtypeStruct((4, n), dtype, sharding=one_chip)
    _compile(functools.partial(lops.sum_chunks, force_kernel=True), x)


def test_quantize_compiles(one_chip):
    n = BUCKET_BYTES // 4
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((n,), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((n // qops.QBLOCK,), jnp.float32,
                             sharding=one_chip)
    _compile(functools.partial(qops.quantize, force_kernel=True), x)
    _compile(functools.partial(qops.dequantize, force_kernel=True), q, s)
    _compile(functools.partial(qops.dequant_add, force_kernel=True), x, q, s)
