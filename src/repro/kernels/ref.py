"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

These are deliberately naive (gather everything, masked softmax) — tests
sweep shapes/dtypes and assert_allclose kernels (interpret=True) against
these.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths):
    """q: (B, H, hd); pages: (P, Hkv, page, hd) head-major;
    block_tables: (B, maxp); lengths: (B,).  A row of length 0 attends to
    nothing and returns zeros.  Returns (B, H, hd)."""
    b, h, hd = q.shape
    hkv, page = k_pages.shape[1], k_pages.shape[2]
    g = h // hkv
    maxp = block_tables.shape[1]

    def linear(pages):
        # (B, maxp, Hkv, page, hd) -> token-major (B, maxp*page, Hkv, hd)
        return pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
            b, maxp * page, hkv, hd)

    k, v = linear(k_pages), linear(v_pages)
    q4 = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", q4, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    pos = jnp.arange(maxp * page)[None, :]
    mask = pos < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    o = jnp.where(lengths[:, None, None, None] > 0, o, 0.0)
    return o.reshape(b, h, hd).astype(q.dtype)


def packed_verify_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                                row_seg):
    """Packed speculative-verify oracle: rows sharing a request share a
    block-table row via ``row_seg``.  q: (R, H, hd); pages:
    (P, Hkv, page, hd); block_tables: (S, maxp); lengths/row_seg: (R,).
    Gathers each row's table and then applies the exact
    ``paged_decode_attention_ref`` math.  Returns (R, H, hd)."""
    return paged_decode_attention_ref(q, k_pages, v_pages,
                                      block_tables[row_seg], lengths)


def chunked_prefill_attention_ref(q, k_cache, v_cache, cache_lens):
    """Chunked-prefill attention: the new chunk's K/V are ALREADY written
    into the cache at [cache_lens - Sq, cache_lens).

    q: (B, Sq, H, hd) — queries of the chunk; k/v_cache: (B, Hkv, Smax, hd)
    head-major; cache_lens: (B,) total valid tokens INCLUDING the chunk.
    Query row j sits at absolute position cache_lens - Sq + j and attends
    causally.  Returns (B, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    q5 = q.reshape(b, sq, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bksd->bkgqs", q5, k_cache.astype(jnp.float32))
    s = s / math.sqrt(hd)
    q_pos = (cache_lens[:, None] - sq + jnp.arange(sq)[None, :])   # (B, Sq)
    k_pos = jnp.arange(smax)[None, :]
    mask = k_pos[:, None, :] <= q_pos[..., None]                   # (B,Sq,Smax)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bqkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def block_gather_ref(pool, indices):
    """pool: (P, *page_shape); indices: (n,) -> (n, *page_shape)."""
    return pool[indices]


def kv_block_quantize_ref(blocks):
    """Symmetric int8 per-(block, layer, k|v)-plane quantization.
    blocks: (n, L, 2, Hkv, bs, hd) -> (int8 vals same shape, fp32 scales
    (n, L, 2)).  Expression shapes deliberately mirror kv_quant.py so the
    kernel is BITWISE equal in interpret mode."""
    n, lyr, two = blocks.shape[:3]
    x = blocks.reshape(n * lyr * two, -1).astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) * (1.0 / 127.0)
    inv = jnp.where(scale > 0.0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv), -127.0, 127.0).astype(jnp.int8)
    return q.reshape(blocks.shape), scale.reshape(n, lyr, two)


def kv_block_dequantize_ref(vals, scales):
    """vals: (n, L, 2, Hkv, bs, hd) int8, scales: (n, L, 2) -> fp32
    blocks.  Roundtrip error vs the original is bounded by scale/2 per
    element (see kv_quant.py)."""
    n, lyr, two = vals.shape[:3]
    q = vals.reshape(n * lyr * two, -1)
    out = q.astype(jnp.float32) * scales.reshape(n * lyr * two, 1)
    return out.reshape(vals.shape)
