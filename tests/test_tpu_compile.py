"""Compile every Pallas kernel of the serving path for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse (block
shapes that do not tile, more fast memory than a kernel may use).  The
shapes are Qwen1.5-0.5B's serving widths — 16 KV heads of head_dim 64 —
at both pool block sizes and both KV dtypes.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so a worker that loads it
while collecting would make the others fail to collect.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_gather import block_gather
from repro.kernels.chunked_prefill import (chunked_prefill_attention,
                                           packed_prefill_attention)
from repro.kernels.kv_quant import kv_block_dequantize, kv_block_quantize
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.spec_verify import packed_verify_attention

pytestmark = pytest.mark.kernel

# Qwen1.5-0.5B attention widths (configs/qwen1_5_0_5b.py)
LAYERS, HEADS, KV_HEADS, HD = 24, 16, 16, 64
POOL_BLOCKS = 512
DTYPES = [jnp.float32, jnp.bfloat16]
BLOCK_SIZES = [16, 128]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but can never be read back without the chip: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bs", BLOCK_SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_decode_compiles(one_chip, dtype, bs):
    b, maxp = 16, 2048 // bs
    _compile(lambda q, k, v, t, n: paged_decode_attention(q, k, v, t, n),
             one_chip,
             ((b, HEADS, HD), dtype),
             ((POOL_BLOCKS, KV_HEADS, bs, HD), dtype),
             ((POOL_BLOCKS, KV_HEADS, bs, HD), dtype),
             ((b, maxp), jnp.int32), ((b,), jnp.int32))


@pytest.mark.parametrize("maxp", [192, 256])
def test_paged_decode_compiles_at_cell_buckets(one_chip, maxp):
    """The benchmark cell's largest decode buckets (24 rows, float32 KV in
    16-token pages, a 1,024-page pool) fit the kernel's VMEM, and the
    custom call keeps the name the benchmark's roofline reader matches."""
    b = 24
    compiled = _compile(
        lambda q, k, v, t, n: paged_decode_attention(q, k, v, t, n),
        one_chip,
        ((b, HEADS, HD), jnp.float32),
        ((1024, KV_HEADS, 16, HD), jnp.float32),
        ((1024, KV_HEADS, 16, HD), jnp.float32),
        ((b, maxp), jnp.int32), ((b,), jnp.int32))
    assert re.search(r"%paged_decode_attention(\.\d+)? = .*"
                     r'custom_call_target="tpu_custom_call"',
                     compiled.as_text())


@pytest.mark.parametrize("bs", BLOCK_SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_packed_verify_compiles(one_chip, dtype, bs):
    rows, segs, maxp = 32, 9, 2048 // bs
    _compile(lambda q, k, v, t, n, s: packed_verify_attention(
                 q, k, v, t, n, s),
             one_chip,
             ((rows, HEADS, HD), dtype),
             ((POOL_BLOCKS, KV_HEADS, bs, HD), dtype),
             ((POOL_BLOCKS, KV_HEADS, bs, HD), dtype),
             ((segs, maxp), jnp.int32), ((rows,), jnp.int32),
             ((rows,), jnp.int32))


@pytest.mark.parametrize("smax,kv_block", [(256, 256), (1024, 512)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_packed_prefill_compiles(one_chip, dtype, smax, kv_block):
    segs, sq = 4, 128
    _compile(lambda q, k, v, c: packed_prefill_attention(
                 q, k, v, c, kv_block=kv_block),
             one_chip,
             ((segs, sq, HEADS, HD), dtype),
             ((segs, KV_HEADS, smax, HD), dtype),
             ((segs, KV_HEADS, smax, HD), dtype),
             ((segs,), jnp.int32))


@pytest.mark.parametrize("dtype", DTYPES)
def test_chunked_prefill_compiles(one_chip, dtype):
    b, sq, smax = 1, 128, 1024
    _compile(lambda q, k, v, n: chunked_prefill_attention(
                 q, k, v, n, kv_block=512),
             one_chip,
             ((b, sq, HEADS, HD), dtype),
             ((b, KV_HEADS, smax, HD), dtype),
             ((b, KV_HEADS, smax, HD), dtype),
             ((b,), jnp.int32))


@pytest.mark.parametrize("bs", BLOCK_SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_kv_quant_pair_compiles(one_chip, dtype, bs):
    blocks = (8, LAYERS, 2, KV_HEADS, bs, HD)
    _compile(lambda x: kv_block_quantize(x), one_chip, (blocks, dtype))
    _compile(lambda v, s: kv_block_dequantize(v, s), one_chip,
             (blocks, jnp.int8), (blocks[:3], jnp.float32))


@pytest.mark.parametrize("bs", BLOCK_SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_block_gather_compiles(one_chip, dtype, bs):
    _compile(lambda p, i: block_gather(p, i), one_chip,
             ((POOL_BLOCKS, KV_HEADS, bs, HD), dtype), ((8,), jnp.int32))
