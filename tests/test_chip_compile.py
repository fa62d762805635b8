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
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import ops as fops
from repro.kernels.local_reduce import ops as lops
from repro.kernels.paged_attention import kernel as pkernel
from repro.kernels.paged_attention import ops as pops
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


@pytest.mark.parametrize("hkv,d,dtype", [
    (8, 128, jnp.bfloat16), (4, 128, jnp.bfloat16), (2, 256, jnp.bfloat16),
    (1, 128, jnp.float32), (1, 128, jnp.bfloat16), (8, 192, jnp.bfloat16),
    (8, 64, jnp.float32)])
def test_paged_attention_fits_where_the_compiler_agrees(one_chip, hkv, d,
                                                        dtype):
    # the ops route every other layout to the oracle
    b, pps, pt = 4, 8, 16
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=one_chip)
    pages = s((b * pps + 1, pt, 2, hkv, d))
    args = (s((b, 2 * hkv, d)), pages, pages, s((), jnp.int32),
            s((b,), jnp.int32), s((b, pps), jnp.int32), s((b, hkv, d)),
            s((b, hkv, d)))
    fn = functools.partial(pops.paged_attention, force_kernel=True)
    if pkernel.fits(pages):
        _compile(fn, *args)
    else:
        with pytest.raises(Exception, match="aligned to tiling"):
            jax.jit(fn).lower(*args).compile()


def test_paged_attention_compiles(one_chip):
    # the serving cell's decode: 32 slots, 64 query / 8 KV heads of 128,
    # 16-token pages, 128 pages a slot, 4 layers a page, bf16
    b, pps, pt = 32, 128, 16
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    pages = s((b * pps + 1, pt, 4, 8, 128))
    _compile(functools.partial(pops.paged_attention, force_kernel=True),
             s((b, 64, 128)), pages, pages, s((), jnp.int32),
             s((b,), jnp.int32), s((b, pps), jnp.int32), s((b, 8, 128)),
             s((b, 8, 128)))


@pytest.mark.parametrize("chips", [1, 4])
def test_pool_decode_program_compiles_on_its_mesh(one_chip, topo, chips,
                                                  monkeypatch):
    # the program the page pool binds for a GQA model the kernel takes,
    # as on the chip: on one chip the paged decode with the kernel, over
    # a mesh the arena program (a Mosaic kernel is not partitioned)
    from repro import comm
    from repro.models import build_model
    from repro.models.layers import AttentionCfg, MLPCfg
    from repro.models.transformer import LayerSpec, StageSpec, TransformerCfg
    from repro.serve.engine import (ServeCfg, make_decode_step,
                                    make_paged_decode_step)
    from repro.serve.paging import PagePool
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, b = 256, 4
    model = build_model(TransformerCfg(
        name="gqa", d_model=d, vocab_size=256,
        stages=(StageSpec((LayerSpec("attn", "dense"),), repeat=2),),
        attn=AttentionCfg(d_model=d, num_heads=4, num_kv_heads=2,
                          head_dim=128, qkv_bias=True),
        mlp=MLPCfg(d, 512, "swiglu"), param_dtype=jnp.bfloat16))
    cfg = ServeCfg(max_len=64, batch=b, page_tokens=16,
                   cache_dtype=jnp.bfloat16)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1),
                ("data", "model"))
    session = comm.Session(mesh=mesh)
    pool = PagePool(model, cfg, comm=session.world)
    assert pool.paged == (chips == 1)
    run = pool.bind_decode(make_decode_step(model, cfg),
                           make_paged_decode_step(model, cfg))
    ints = np.zeros((b,), np.int32)
    args = (jax.eval_shape(model.init, jax.random.PRNGKey(0)), pool.pool,
            pool.state,
            pool.pack_decode(ints, ints, ints, [None] * b, [False] * b))
    rep = NamedSharding(mesh, P())
    structs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), args)
    with session.activate():
        text = run.program.lower(*structs).compile().as_text()
    assert ("tpu_custom_call" in text) == (chips == 1)
