"""Paged flash-decode attention — the serving engine's decode hot path.

ProServe's block manager stores KV in fixed-size pages with per-request
block tables (§4.3); this kernel runs one decode step for a batch of
requests directly against the paged pool:

  * grid = (batch, kv_head, page_step) — the page dimension iterates
    sequentially on-core, maintaining an online softmax in VMEM scratch
    (flash-decode), so nothing larger than one (page, head_dim) tile plus
    the (G, head_dim) accumulator ever sits in VMEM;
  * pages are HEAD-MAJOR, ``(P, Hkv, page, hd)``: one (page, hd) tile is
    the trailing two dims of the array, which is what lets the TPU
    compiler tile a one-head block (a token-major ``(P, page, Hkv, hd)``
    page puts Hkv second-to-last and a one-head block can never tile);
  * page indices are SCALAR-PREFETCHED (PrefetchScalarGridSpec): the block
    table drives the K/V BlockSpec index_map, so each grid step DMAs
    exactly the page it needs — the TPU analogue of vLLM's gather, with no
    materialized (B, S, ...) contiguous KV;
  * GQA: the G = H/Hkv query heads of a kv group are processed together as
    the row dimension of the (G, page) score tile.

TPU mapping notes: a page size that is a multiple of 8 (sublanes) and a
head_dim of 128 (lanes) fill whole tiles; G < 8 underfills the MXU
sublane dim — acceptable for decode, which is DMA-bound anyway.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # --- scalar prefetch ---
    block_tables_ref,    # (B, maxp) int32
    lengths_ref,         # (B,) int32
    # --- blocked operands ---
    q_ref,               # (G, hd)
    k_ref,               # (page, hd)
    v_ref,               # (page, hd)
    # --- blocked output ---
    o_ref,               # (G, hd)
    # --- scratch ---
    m_ref,               # (G, 1) f32
    l_ref,               # (G, 1) f32
    acc_ref,             # (G, hd) f32
    *, page: int, max_pages: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                     # (G, hd)
    k = k_ref[...].astype(jnp.float32)                     # (page, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / math.sqrt(q.shape[-1]))                 # (G, page)

    pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < lengths_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                    # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)          # (G, page)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == max_pages - 1)
    def _out():
        o_ref[...] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           *, interpret: bool = False):
    """q: (B, H, hd); k/v_pages: (P, Hkv, page, hd) head-major pages;
    block_tables: (B, maxp) int32 (pad with 0); lengths: (B,) int32.
    Returns (B, H, hd)."""
    b, h, hd = q.shape
    n_pages, hkv, page, _ = k_pages.shape
    g = h // hkv
    maxp = block_tables.shape[1]
    q4 = q.reshape(b, hkv, g, hd)

    grid = (b, hkv, maxp)

    def q_map(bi, hi, ii, bt, ln):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, ii, bt, ln):
        return (bt[bi, ii], hi, 0, 0)

    def o_map(bi, hi, ii, bt, ln):
        return (bi, hi, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, page=page, max_pages=maxp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, g, hd), q_map),
                pl.BlockSpec((None, None, page, hd), kv_map),
                pl.BlockSpec((None, None, page, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((None, None, g, hd), o_map),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q4, k_pages, v_pages)
    return out.reshape(b, h, hd)
