"""Chunked-prefill flash attention over the serving KV cache.

SlideBatching admits prefill in CHUNKS sized by the latency budget (Alg. 1
GetMaxChunk); the engine writes the chunk's K/V into the cache and then
calls this kernel: queries of the chunk attend to everything already in
the cache (prefix) plus the chunk itself, causally.

  * grid = (batch, kv_head, kv_step): kv_step walks the cache in blocks,
    online softmax in VMEM scratch (flash);
  * the staged cache is HEAD-MAJOR, ``(B, Hkv, Smax, hd)``, so one
    (kv_block, hd) tile is the array's trailing two dims — the layout the
    TPU compiler can tile for a one-head block;
  * the (G·Sq, kv_block) score tile keeps the MXU busy even for small
    chunks (G query heads per kv head stacked into rows);
  * per-request total lengths are scalar-prefetched; rows are masked
    causally against absolute positions, so ragged batches of different
    context lengths run in one call.
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
    lengths_ref,        # (B,) int32 — total valid tokens incl. the chunk
    q_ref,              # (1, 1, G, Sq, hd)
    k_ref,              # (kvb, hd)
    v_ref,              # (kvb, hd)
    o_ref,              # (1, 1, G, Sq, hd)
    m_ref,              # (G*Sq, 1) f32
    l_ref,              # (G*Sq, 1) f32
    acc_ref,            # (G*Sq, hd) f32
    *, kv_block: int, n_steps: int, sq: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = q_ref.shape[2]
    hd = q_ref.shape[-1]
    q = q_ref[0, 0].astype(jnp.float32).reshape(g * sq, hd)
    k = k_ref[...].astype(jnp.float32)                 # (kvb, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / math.sqrt(hd))                          # (G*Sq, kvb)

    total = lengths_ref[b]
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % sq
    q_pos = total - sq + row                               # absolute q pos
    k_pos = i * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = k_pos <= q_pos                                 # causal + length
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == n_steps - 1)
    def _out():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = o.reshape(g, sq, hd).astype(o_ref.dtype)


def _packed_kernel(
    ctx_lens_ref,       # (S,) int32 — cached tokens BEFORE each chunk
    q_ref,              # (1, 1, G, Sq, hd)
    k_ref,              # (kvb, hd)
    v_ref,              # (kvb, hd)
    o_ref,              # (1, 1, G, Sq, hd)
    m_ref,              # (G*Sq, 1) f32
    l_ref,              # (G*Sq, 1) f32
    acc_ref,            # (G*Sq, hd) f32
    *, kv_block: int, n_steps: int, sq: int,
):
    """Packed multi-request prefill: one grid row per SEGMENT (request
    chunk).  Queries sit at absolute positions ``ctx_lens[b] + row``; the
    staged cache holds only the blocks this segment needs, so KV tiles
    entirely beyond the segment's causal horizon are skipped (the online
    softmax state is untouched — a bitwise no-op, see tests)."""
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_lens_ref[b]

    # last causally-visible position of this segment is ctx + sq - 1; tiles
    # starting beyond it contribute exactly nothing — skip their FLOPs.
    @pl.when(i * kv_block <= ctx + sq - 1)
    def _accumulate():
        g = q_ref.shape[2]
        hd = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32).reshape(g * sq, hd)
        k = k_ref[...].astype(jnp.float32)             # (kvb, hd)
        v = v_ref[...].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(hd))                      # (G*Sq, kvb)

        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % sq
        q_pos = ctx + row                                  # absolute q pos
        k_pos = i * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                        1)
        valid = k_pos <= q_pos                             # causal + length
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_steps - 1)
    def _out():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        g = q_ref.shape[2]
        hd = q_ref.shape[-1]
        o_ref[0, 0] = o.reshape(g, sq, hd).astype(o_ref.dtype)


def packed_prefill_attention(q, k_cache, v_cache, ctx_lens,
                             *, kv_block: int = 512,
                             interpret: bool = False):
    """Multi-request packed prefill attention (one call, S segments).

    q: (S, Sq, H, hd) — per-segment chunk queries, right-padded to a common
    ``Sq`` (padded rows are masked out by the consumer); k/v_cache:
    (S, Hkv, Smax, hd) head-major staged per-segment caches with each chunk's K/V
    already written at [ctx, ctx+chunk); ctx_lens: (S,) cached tokens
    BEFORE each chunk.  Query row r of segment s sits at absolute position
    ``ctx_lens[s] + r`` — identical masking to the per-request kernel with
    ``cache_lens = ctx_lens + Sq``, so per-segment results are bitwise
    equal to S separate ``chunked_prefill_attention`` calls.
    Returns (S, Sq, H, hd)."""
    s_, sq, h, hd = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    n_steps = -(-smax // kv_block)
    if smax % kv_block:
        padlen = n_steps * kv_block - smax
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, padlen), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, padlen), (0, 0)))
    q5 = q.reshape(s_, sq, hkv, g, hd).transpose(0, 2, 3, 1, 4)

    grid = (s_, hkv, n_steps)

    def q_map(bi, hi, ii, ln):
        return (bi, hi, 0, 0, 0)

    def kv_map(bi, hi, ii, ln):
        return (bi, hi, ii, 0)

    out = pl.pallas_call(
        functools.partial(_packed_kernel, kv_block=kv_block,
                          n_steps=n_steps, sq=sq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, sq, hd), q_map),
                pl.BlockSpec((None, None, kv_block, hd), kv_map),
                pl.BlockSpec((None, None, kv_block, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g, sq, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((g * sq, 1), jnp.float32),
                pltpu.VMEM((g * sq, 1), jnp.float32),
                pltpu.VMEM((g * sq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s_, hkv, g, sq, hd), q.dtype),
        interpret=interpret,
    )(ctx_lens, q5, k_cache, v_cache)
    return out.transpose(0, 3, 1, 2, 4).reshape(s_, sq, h, hd)


def chunked_prefill_attention(q, k_cache, v_cache, cache_lens,
                              *, kv_block: int = 512,
                              interpret: bool = False):
    """q: (B, Sq, H, hd); k/v_cache: (B, Hkv, Smax, hd) with the chunk's K/V
    already written at [len-Sq, len); cache_lens: (B,) valid lengths
    INCLUDING the chunk.  Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    n_steps = -(-smax // kv_block)
    if smax % kv_block:
        padlen = n_steps * kv_block - smax
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, padlen), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, padlen), (0, 0)))
    # (B, Sq, H, hd) -> (B, Hkv, G, Sq, hd)
    q5 = q.reshape(b, sq, hkv, g, hd).transpose(0, 2, 3, 1, 4)

    grid = (b, hkv, n_steps)

    def q_map(bi, hi, ii, ln):
        return (bi, hi, 0, 0, 0)

    def kv_map(bi, hi, ii, ln):
        return (bi, hi, ii, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, kv_block=kv_block, n_steps=n_steps,
                          sq=sq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, sq, hd), q_map),
                pl.BlockSpec((None, None, kv_block, hd), kv_map),
                pl.BlockSpec((None, None, kv_block, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g, sq, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((g * sq, 1), jnp.float32),
                pltpu.VMEM((g * sq, 1), jnp.float32),
                pltpu.VMEM((g * sq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, sq, hd), q.dtype),
        interpret=interpret,
    )(cache_lens, q5, k_cache, v_cache)
    # (B, Hkv, G, Sq, hd) -> (B, Sq, H, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
