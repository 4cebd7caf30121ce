"""Jitted model execution against the paged KV pool.

Entry points, all shape-bucketed to bound recompilation:

* ``decode_step`` / ``decode_batch`` — one token for B requests: per
  layer, project QKV, scatter the new K/V into each request's current
  block slot, run paged flash-decode attention (the Pallas kernel,
  compiled on TPU and interpreted on any other backend).
* ``verify_step`` — the packed speculative-verify variant of decode.
* ``prefill_packed`` / ``prefill_chunk`` — chunked prefill (Alg. 1) for
  several requests in one flat stream, or for ONE request: write the new
  K/V, stage each request's context, run chunked-prefill flash attention.

The pool is ``(L, 2, P, Hkv, bs, hd)``: each page is head-major so the
kernels' one-head ``(bs, hd)`` tiles are the array's trailing two dims.

Only dense/GQA families are supported by the real engine demo
(qwen1.5-0.5b smoke-scale is the example model); the simulator covers all
families at paper scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import (chunked_prefill_attention,
                           packed_prefill_attention, packed_verify_attention,
                           paged_decode_attention)
from ..models.layers import apply_norm, apply_rope, gelu_mlp, swiglu
from ..models.model import ArchConfig, _qkv


def _mlp(cfg, lp, h):
    if cfg.family == "moe":
        from ..models.moe import moe_forward
        return moe_forward(h, lp["moe"], cfg.top_k, cfg.capacity_factor)
    return swiglu(h, lp["mlp"]) if cfg.act == "swiglu" else gelu_mlp(h, lp["mlp"])


def _write_kv(layer_kv, blocks, slots, k, v):
    """Scatter per-token K/V ``(N, Hkv, hd)`` into a layer's head-major
    pages ``(2, P, Hkv, bs, hd)`` at (block, slot) of each token."""
    layer_kv = layer_kv.at[0, blocks, :, slots].set(k)
    return layer_kv.at[1, blocks, :, slots].set(v)


def _stage(pages, tables):
    """Gather the pages of each table row into a contiguous head-major
    stage: pages ``(P, Hkv, bs, hd)``, tables ``(S, maxp)`` ->
    ``(S, Hkv, maxp*bs, hd)``."""
    g = pages[tables]                                     # (S, maxp, Hkv, bs, hd)
    s_, maxp, hkv, bs, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(s_, hkv, maxp * bs, hd)


def _decode_forward(cfg: ArchConfig, params, pool_kv, tokens, tables, lens):
    """Shared decode forward pass (traced by both ``decode_batch`` and the
    fused ``decode_step`` so the two jit variants run the identical graph).
    tokens: (B,) int32; tables: (B, maxp); lens: (B,) context BEFORE this
    step.  Returns (logits (B, V), new pool)."""
    b = tokens.shape[0]
    bs = pool_kv.shape[4]
    x = params["embed"][tokens][:, None, :].astype(pool_kv.dtype)
    positions = lens[:, None]
    block_of = tables[jnp.arange(b), lens // bs]          # (B,)
    slot_of = lens % bs

    def layer(carry, xs):
        x, pool = carry
        lp, li = xs["p"], xs["i"]
        h = apply_norm(x, lp["ln1"], cfg.norm)
        q, k, v = _qkv(cfg, lp["attn"], h)
        if cfg.rope_fraction > 0:
            q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
        # write the new K/V into each request's current block slot
        layer_kv = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
        layer_kv = _write_kv(layer_kv, block_of, slot_of, k[:, 0], v[:, 0])
        pool = jax.lax.dynamic_update_index_in_dim(pool, layer_kv, li, 0)
        o = paged_decode_attention(q[:, 0], layer_kv[0], layer_kv[1],
                                   tables, lens + 1)
        a_out = jnp.einsum("bk,kd->bd", o.reshape(b, -1),
                           lp["attn"]["wo"])[:, None]
        x = x + a_out
        h2 = apply_norm(x, lp["ln2"], cfg.norm)
        x = x + _mlp(cfg, lp, h2)
        return (x, pool), None

    xs = {"p": params["layers"],
          "i": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
    (x, pool_kv), _ = jax.lax.scan(layer, (x, pool_kv), xs)
    x = apply_norm(x, params["ln_f"], cfg.norm)
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])[:, 0]
    return logits, pool_kv


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def decode_batch(cfg: ArchConfig, params, pool_kv, tokens, tables, lens):
    """One token for B requests, returning the full logits for host-side
    sampling.  tokens: (B,) int32; tables: (B, maxp); lens: (B,) context
    BEFORE this step.  Returns (logits (B, V), new pool)."""
    return _decode_forward(cfg, params, pool_kv, tokens, tables, lens)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def decode_step(cfg: ArchConfig, params, pool_kv, tokens, tables, lens):
    """Fused decode step: the same forward pass as ``decode_batch`` with
    the greedy argmax folded into the jitted graph, so the device->host
    fetch shrinks from (B, V) float logits to (B,) int32 tokens.

    The batch dimension may be padded to a bucket (``seg_bucket``) and the
    table width to ``table_bucket``: padding rows carry token 0, length 0
    and an all-zero table row, so their single K/V write lands in the
    reserved null block 0 (the packed-prefill convention) and their output
    token is garbage the caller discards.  Real rows are unaffected — every
    per-row computation is independent and the paged-attention kernel
    reads no table entry past ``lens``."""
    logits, pool_kv = _decode_forward(cfg, params, pool_kv, tokens, tables,
                                      lens)
    return jnp.argmax(logits, -1).astype(jnp.int32), pool_kv


def _verify_forward(cfg: ArchConfig, params, pool_kv, tokens, tables, lens,
                    row_seg):
    """Packed speculative-verify forward: ``_decode_forward`` over an
    EXPANDED row set — one row per (request, draft position j), where row
    j carries the token at position l_kv + j and ``lens`` = l_kv + j.
    ``tables`` stays compact at (S, maxp): ``row_seg`` maps each row to
    its request's table row (the packed-verify kernel reads it via
    scalar prefetch; the K/V scatter gathers it host-of-kernel).

    Causality inside one launch follows the decode convention: every
    row's K/V is scattered BEFORE attention within each layer, and row
    j's length mask (lens + 1) covers exactly rows <= j of its own
    request — so row j+1 attends to row j's same-launch write and the
    packed rows reproduce sequential greedy decode bitwise."""
    r = tokens.shape[0]
    bs = pool_kv.shape[4]
    x = params["embed"][tokens][:, None, :].astype(pool_kv.dtype)
    positions = lens[:, None]
    row_tables = tables[row_seg]                          # (R, maxp)
    block_of = row_tables[jnp.arange(r), lens // bs]      # (R,)
    slot_of = lens % bs

    def layer(carry, xs):
        x, pool = carry
        lp, li = xs["p"], xs["i"]
        h = apply_norm(x, lp["ln1"], cfg.norm)
        q, k, v = _qkv(cfg, lp["attn"], h)
        if cfg.rope_fraction > 0:
            q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
        layer_kv = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
        layer_kv = _write_kv(layer_kv, block_of, slot_of, k[:, 0], v[:, 0])
        pool = jax.lax.dynamic_update_index_in_dim(pool, layer_kv, li, 0)
        o = packed_verify_attention(q[:, 0], layer_kv[0], layer_kv[1],
                                    tables, lens + 1, row_seg)
        a_out = jnp.einsum("bk,kd->bd", o.reshape(r, -1),
                           lp["attn"]["wo"])[:, None]
        x = x + a_out
        h2 = apply_norm(x, lp["ln2"], cfg.norm)
        x = x + _mlp(cfg, lp, h2)
        return (x, pool), None

    xs = {"p": params["layers"],
          "i": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
    (x, pool_kv), _ = jax.lax.scan(layer, (x, pool_kv), xs)
    x = apply_norm(x, params["ln_f"], cfg.norm)
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])[:, 0]
    return logits, pool_kv


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def verify_step(cfg: ArchConfig, params, pool_kv, tokens, tables, lens,
                row_seg):
    """Fused speculative-verify step: greedy argmax for all packed rows
    in one launch.  tokens/lens/row_seg: (R,) int32 (row-bucket padded);
    tables: (S, maxp) int32 (segment-bucket padded).  Padding rows carry
    token 0, length 0 and point at an all-zero pad table row, so their
    K/V write lands in the reserved null block 0 (decode_step
    convention) and their output token is discarded by the caller.
    Returns ((R,) int32 argmax tokens, new pool)."""
    logits, pool_kv = _verify_forward(cfg, params, pool_kv, tokens, tables,
                                      lens, row_seg)
    return jnp.argmax(logits, -1).astype(jnp.int32), pool_kv


@functools.partial(jax.jit, static_argnums=(0, 6), donate_argnums=(2,))
def prefill_chunk(cfg: ArchConfig, params, pool_kv, tokens, table, ctx_len,
                  max_ctx: int):
    """One request's chunk.  tokens: (1, c) int32 (pad with 0 to the
    bucket size); table: (1, maxp); ctx_len: (1,) tokens already cached;
    ``max_ctx``: static staging size (>= ctx+chunk).  Returns
    (last-position logits (1, V), new pool, valid_len)."""
    c = tokens.shape[1]
    bs = pool_kv.shape[4]
    x = params["embed"][tokens].astype(pool_kv.dtype)
    positions = ctx_len[:, None] + jnp.arange(c)[None, :]
    maxp_stage = max_ctx // bs

    def layer(carry, xs):
        x, pool = carry
        lp, li = xs["p"], xs["i"]
        h = apply_norm(x, lp["ln1"], cfg.norm)
        q, k, v = _qkv(cfg, lp["attn"], h)
        if cfg.rope_fraction > 0:
            q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
        layer_kv = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
        # scatter the chunk's K/V into pool blocks position by position
        pos = ctx_len[0] + jnp.arange(c)
        blocks = table[0, pos // bs]
        slots = pos % bs
        layer_kv = _write_kv(layer_kv, blocks, slots, k[0], v[0])
        pool = jax.lax.dynamic_update_index_in_dim(pool, layer_kv, li, 0)
        # stage the context (gather blocks) into a contiguous buffer
        stage_blocks = table[:, :maxp_stage]
        k_stage = _stage(layer_kv[0], stage_blocks)
        v_stage = _stage(layer_kv[1], stage_blocks)
        o = chunked_prefill_attention(q, k_stage, v_stage, ctx_len + c)
        a_out = jnp.einsum("bsk,kd->bsd", o.reshape(1, c, -1),
                           lp["attn"]["wo"])
        x = x + a_out
        h2 = apply_norm(x, lp["ln2"], cfg.norm)
        x = x + _mlp(cfg, lp, h2)
        return (x, pool), None

    xs = {"p": params["layers"],
          "i": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
    (x, pool_kv), _ = jax.lax.scan(layer, (x, pool_kv), xs)
    x = apply_norm(x, params["ln_f"], cfg.norm)
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])
    return logits, pool_kv


@functools.partial(jax.jit, static_argnums=(0, 12, 13), donate_argnums=(2,))
def prefill_packed(cfg: ArchConfig, params, pool_kv, tokens, positions,
                   q_rows, q_cols, scatter_blocks, scatter_slots, tables,
                   ctx_lens, last_idx, smax: int, sq: int):
    """Packed multi-request prefill: several requests' chunks concatenated
    into ONE flat token stream and executed in a single jitted call.

    The dense ops (embedding, QKV/output projections, MLP) run directly on
    the packed stream — no padding FLOPs.  Attention regroups queries into
    a per-segment padded layout and stages only the blocks each segment
    actually needs (``smax`` covers the longest segment, not the engine-wide
    ``max_ctx``), then runs the packed Pallas kernel.

      tokens:          (1, T) int32 flat stream, 0-padded to the T bucket
      positions:       (1, T) absolute position of each token (pad: 0)
      q_rows / q_cols: (T,)  attention scatter target: segment row /
                       within-chunk offset.  Padding tokens point at the
                       extra row ``S`` so they never touch real queries.
      scatter_blocks / scatter_slots: (T,) physical KV destination of each
                       token (padding tokens write the null block 0)
      tables:          (S, smax // block_size) staging tables (pad rows: 0)
      ctx_lens:        (S,) tokens already cached before each chunk
      last_idx:        (S,) flat index of each segment's last real token
      smax, sq:        static staging length / chunk-pad length

    Returns (last-position logits per segment (S, V), new pool)."""
    t_len = tokens.shape[1]
    n_seg = tables.shape[0]
    x = params["embed"][tokens].astype(pool_kv.dtype)      # (1, T, d)

    def layer(carry, xs):
        x, pool = carry
        lp, li = xs["p"], xs["i"]
        h = apply_norm(x, lp["ln1"], cfg.norm)
        q, k, v = _qkv(cfg, lp["attn"], h)                 # (1, T, H|Hkv, hd)
        if cfg.rope_fraction > 0:
            q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
        layer_kv = jax.lax.dynamic_index_in_dim(pool, li, 0, keepdims=False)
        # one flat scatter writes every segment's chunk K/V
        layer_kv = _write_kv(layer_kv, scatter_blocks, scatter_slots,
                             k[0], v[0])
        pool = jax.lax.dynamic_update_index_in_dim(pool, layer_kv, li, 0)
        # stage each segment's blocks (only the ones it needs)
        k_stage = _stage(layer_kv[0], tables)
        v_stage = _stage(layer_kv[1], tables)
        # regroup flat queries into the padded per-segment layout; the
        # extra row n_seg absorbs padding tokens
        q_pad = jnp.zeros((n_seg + 1, sq) + q.shape[2:], q.dtype)
        q_pad = q_pad.at[q_rows, q_cols].set(q[0])
        # kv_block matched to the staging length: a fixed 512 would pad
        # every segment's scores 4x when smax is 128 (masked positions are
        # bitwise no-ops, but their FLOPs are real)
        o = packed_prefill_attention(q_pad[:n_seg], k_stage, v_stage,
                                     ctx_lens, kv_block=min(512, smax))
        o_ext = jnp.concatenate(
            [o, jnp.zeros((1,) + o.shape[1:], o.dtype)], axis=0)
        o_flat = o_ext[q_rows, q_cols]                     # (T, H, hd)
        a_out = jnp.einsum("tk,kd->td", o_flat.reshape(t_len, -1),
                           lp["attn"]["wo"])[None]
        x = x + a_out
        h2 = apply_norm(x, lp["ln2"], cfg.norm)
        x = x + _mlp(cfg, lp, h2)
        return (x, pool), None

    xs = {"p": params["layers"],
          "i": jnp.arange(cfg.n_layers, dtype=jnp.int32)}
    (x, pool_kv), _ = jax.lax.scan(layer, (x, pool_kv), xs)
    x = apply_norm(x, params["ln_f"], cfg.norm)
    # only each segment's LAST chunk token can be sampled — skip the
    # (V x T) logit matmul for every other position
    x_last = x[0, last_idx]                                # (S, d)
    logits = jnp.einsum("sd,vd->sv", x_last, params["lm_head"])
    return logits, pool_kv


def pack_prefill(chunks, bs: int):
    """Host-side packing of ``prefill_packed``'s inputs.

    ``chunks``: one ``(tokens, ctx, table)`` per request — the chunk's
    token ids, the tokens already cached before it, and the request's
    physical block table (covering ctx + len(tokens)).  Returns
    ``(arrays, smax, sq)`` where ``arrays`` are the numpy operands
    ``tokens, positions, q_rows, q_cols, scatter_blocks, scatter_slots,
    tables, ctx_lens, last_idx`` in ``prefill_packed``'s order."""
    n_seg = len(chunks)
    sq = chunk_bucket(max(len(t) for t, _, _ in chunks))
    smax = chunk_bucket(max(ctx + len(t) for t, ctx, _ in chunks))
    smax = -(-smax // bs) * bs
    maxp = smax // bs
    t_b = flat_bucket(sum(len(t) for t, _, _ in chunks))
    s_b = seg_bucket(n_seg)

    tokens = np.zeros((1, t_b), np.int32)
    positions = np.zeros((1, t_b), np.int32)
    q_rows = np.full((t_b,), s_b, np.int32)       # padding -> extra row
    q_cols = np.zeros((t_b,), np.int32)
    sblocks = np.zeros((t_b,), np.int32)          # padding -> null block 0
    sslots = np.zeros((t_b,), np.int32)
    tables = np.zeros((s_b, maxp), np.int32)
    ctx_lens = np.zeros((s_b,), np.int32)
    last_idx = np.zeros((s_b,), np.int32)
    off = 0
    for i, (toks, ctx, table) in enumerate(chunks):
        n = len(toks)
        tokens[0, off:off + n] = toks
        pos = np.arange(ctx, ctx + n, dtype=np.int32)
        positions[0, off:off + n] = pos
        q_rows[off:off + n] = i
        q_cols[off:off + n] = np.arange(n, dtype=np.int32)
        t = np.asarray(table, np.int32)
        sblocks[off:off + n] = t[pos // bs]
        sslots[off:off + n] = pos % bs
        k = min(len(t), maxp)
        tables[i, :k] = t[:k]
        ctx_lens[i] = ctx
        last_idx[i] = off + n - 1
        off += n
    arrays = (tokens, positions, q_rows, q_cols, sblocks, sslots, tables,
              ctx_lens, last_idx)
    return arrays, smax, sq


def bucket(n: int, buckets=(16, 32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // buckets[-1]) * buckets[-1]


def _geom_bucket(n: int, lo: int) -> int:
    """Round up to the next {2^k, 1.5*2^k} step at or above ``lo``: pad
    waste is bounded at 1.33x while the number of distinct jit variants
    stays logarithmic in n (each static shape recompiles the full model
    forward, so linear-step buckets would explode the variant count)."""
    b = lo
    while True:
        if n <= b:
            return b
        if n <= b + b // 2:
            return b + b // 2
        b <<= 1


def flat_bucket(n: int) -> int:
    """Bucket for the packed flat token stream: power-of-two steps up to
    2048, then geometric half-steps — the coarse 2048-step tail of
    ``bucket`` would pad a 2.3k-token pack to 4k (real FLOPs on every
    dense op)."""
    return bucket(n) if n <= 2048 else _geom_bucket(n, 2048)


def chunk_bucket(n: int) -> int:
    """Bucket for the packed per-segment pad length (sq) and staging span:
    power-of-two steps up to 128, then geometric half-steps — the
    attention score tile is (G*sq, smax), so the plain pow2 tail would pad
    a 160-token chunk's scores by 1.6x."""
    return bucket(n) if n <= 128 else _geom_bucket(n, 128)


def table_bucket(p: int) -> int:
    """Bucket for the decode block-table width (maxp): {2^k, 1.5*2^k}
    steps from 4.  Together with ``seg_bucket`` on the batch dimension this
    makes the decode jit cache persistent across steps — batches of
    (B in 5..6, maxp in 9..12) all hit one compiled variant instead of
    compiling per exact shape."""
    return _geom_bucket(p, 4)


def seg_bucket(s: int) -> int:
    """Bucket for the packed segment count: powers of two up to 8, then
    multiples of 8 (bounds jit variants without padding 24 segments
    to 32)."""
    if s <= 8:
        b = 1
        while b < s:
            b <<= 1
        return b
    return -(-s // 8) * 8
