"""Int8 KV-block (de)quantization for the cold tier of the tiered cache.

Host-tier evictions demote KV blocks into an int8 cold tier (~4x smaller
than fp32, so a cold reload moves ~4x fewer bytes over the host link).
Quantization is symmetric per (block, layer, k|v) *plane*:

    scale = absmax(plane) / 127
    q     = clip(round(x / scale), -127, 127)  as int8
    x'    = q * scale

The per-plane granularity matches the offload wire unit — one KV block is
``(L, 2, Hkv, bs, hd)`` and each of its ``L*2`` planes gets its own fp32
scale — so a single outlier key only widens the step of its own layer's
K (or V) plane, not the whole block.

Error bound: round() contributes at most half a step, so every element
satisfies ``|x - x'| <= scale/2`` (asserted by tests/test_kernels.py).
All ops are elementwise or exact reductions (abs/max), so the kernels are
bitwise-identical to the ``ref.py`` oracles in interpret mode.

Grid: one program per plane row — the input is viewed as ``(R, 1, E)``
with ``R = n*L*2`` rows of ``E = Hkv*bs*hd`` elements and the scales as
``(R, 1, 1)``; each program reduces one row to its scale and writes the
quantized row (quantize) or applies the row's scale (dequantize).  The
unit middle axis makes each ``(1, 1, E)`` block span the array's full
trailing two dims, which the TPU compiler requires of a block whose
row count is not a multiple of 8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, vals_ref, scales_ref):
    x = x_ref[...].astype(jnp.float32)
    # explicit multiply by the constant reciprocal: XLA strength-reduces
    # x/127.0 to this under jit, so spelling it out keeps the jit'd kernel
    # and the eager ref oracle bitwise identical
    scale = jnp.max(jnp.abs(x)) * (1.0 / 127.0)
    inv = jnp.where(scale > 0.0, 1.0 / scale, 0.0)
    vals_ref[...] = jnp.clip(jnp.round(x * inv), -127.0, 127.0).astype(
        jnp.int8)
    scales_ref[...] = jnp.broadcast_to(scale, scales_ref.shape)


def _dequant_kernel(vals_ref, scales_ref, out_ref):
    scale = scales_ref[0, 0, 0]
    out_ref[...] = vals_ref[...].astype(jnp.float32) * scale


def _row_view(blocks):
    n, lyr, two = blocks.shape[:3]
    return blocks.reshape(n * lyr * two, 1, -1)


def _row_map(i):
    return (i, 0, 0)


def kv_block_quantize(blocks, *, interpret: bool = False):
    """blocks: (n, L, 2, Hkv, bs, hd) float -> (int8 vals same shape,
    fp32 scales (n, L, 2))."""
    n, lyr, two = blocks.shape[:3]
    x = _row_view(blocks)
    r, _, e = x.shape

    vals, scales = pl.pallas_call(
        _quant_kernel,
        grid=(r,),
        in_specs=[pl.BlockSpec((1, 1, e), _row_map)],
        out_specs=[pl.BlockSpec((1, 1, e), _row_map),
                   pl.BlockSpec((1, 1, 1), _row_map)],
        out_shape=[jax.ShapeDtypeStruct((r, 1, e), jnp.int8),
                   jax.ShapeDtypeStruct((r, 1, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return (vals.reshape(blocks.shape), scales.reshape(n, lyr, two))


def kv_block_dequantize(vals, scales, *, interpret: bool = False):
    """vals: (n, L, 2, Hkv, bs, hd) int8, scales: (n, L, 2) fp32 ->
    fp32 blocks of vals' shape."""
    q = _row_view(vals)
    r, _, e = q.shape

    out = pl.pallas_call(
        _dequant_kernel,
        grid=(r,),
        in_specs=[pl.BlockSpec((1, 1, e), _row_map),
                  pl.BlockSpec((1, 1, 1), _row_map)],
        out_specs=pl.BlockSpec((1, 1, e), _row_map),
        out_shape=jax.ShapeDtypeStruct((r, 1, e), jnp.float32),
        interpret=interpret,
    )(q, scales.reshape(r, 1, 1))
    return out.reshape(vals.shape)
