"""Packed speculative-verify attention — scoring k+1 draft positions at once.

Greedy speculative decoding verifies a request's draft chain by running
the decode forward for rows j = 0..depth, where row j processes the
token at position l_kv + j.  All rows of one request share the SAME
block table; materializing a (rows, maxp) table would copy each
request's table depth+1 times and make the scalar-prefetch buffer scale
with the packed row count.

This kernel is ``paged_attention._kernel`` with ONE change: a third
scalar-prefetched operand ``row_seg`` maps each verify row to its
request's row in a compact (S, maxp) block table, and the K/V index_map
reads ``bt[seg[bi], ii]`` instead of ``bt[bi, ii]``.  The kernel body —
tile shapes, online-softmax accumulation order, masking — is identical,
so every row's output is bitwise-equal to ``paged_decode_attention``
run with that row's gathered table: the property the engine's
stream-equality guarantee (and tests/test_spec_decode.py) rests on.

Per-row lengths stay a (rows,) vector: row j of a request passes
l_kv + j + 1, which masks out the same-launch KV writes of rows > j —
causality across the packed rows without any extra masking logic.
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
    row_seg_ref,         # (R,) int32: verify row -> block-table row
    block_tables_ref,    # (S, maxp) int32
    lengths_ref,         # (R,) int32
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


def packed_verify_attention(q, k_pages, v_pages, block_tables, lengths,
                            row_seg, *, interpret: bool = False):
    """q: (R, H, hd) — one row per (request, draft position);
    k/v_pages: (P, Hkv, page, hd) head-major pages; block_tables: (S, maxp) int32 (pad
    with 0); lengths: (R,) int32 — per ROW (l_kv + j + 1);
    row_seg: (R,) int32 — row -> block-table row in [0, S).
    Returns (R, H, hd)."""
    b, h, hd = q.shape
    n_pages, hkv, page, _ = k_pages.shape
    g = h // hkv
    maxp = block_tables.shape[1]
    q4 = q.reshape(b, hkv, g, hd)

    grid = (b, hkv, maxp)

    def q_map(bi, hi, ii, seg, bt, ln):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, ii, seg, bt, ln):
        return (bt[seg[bi], ii], hi, 0, 0)

    def o_map(bi, hi, ii, seg, bt, ln):
        return (bi, hi, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, page=page, max_pages=maxp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
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
    )(row_seg, block_tables, lengths, q4, k_pages, v_pages)
    return out.reshape(b, h, hd)
