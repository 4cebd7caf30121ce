"""Paged flash-decode attention — the serving engine's decode hot path.

ProServe's block manager stores KV in fixed-size pages with per-request
block tables (§4.3); this kernel runs one decode step for a batch of
requests directly against the paged pool:

  * pages are HEAD-MAJOR, ``(P, Hkv, page, hd)``, so one page of all KV
    heads is one contiguous block in HBM and one DMA.  The kernel reads
    the pages lane-dense, as ``(P, Hkv, page/r, r*hd)`` with
    ``r = 128 // hd`` tokens per 128-lane row when ``hd`` divides 128: the
    TPU compiler cannot slice a page out of an array narrower than 128
    lanes;
  * grid = (row, page_block): the K/V pools stay in HBM and each grid
    step gathers up to ``ppb`` whole pages of one row, chosen by the
    SCALAR-PREFETCHED block table, with manual async copies into a VMEM
    double buffer.  The copies of the next block — the next row's first
    block at a row's end — start before this block is computed;
  * a row stops at its own length: of its ``ceil(len / (ppb*page))``
    blocks the last copies only the row's own pages (table entries past
    them are never read), and grid steps past the last block issue no DMA
    and do no compute.  A row of length 0 costs nothing and returns zeros;
  * all heads at once: scores are a dot batched over heads,
    ``(Hkv, r*G, r*hd) x (Hkv, ppb*page/r, r*hd) -> (Hkv, r*G, ppb*page/r)``,
    where query row (u, g) holds query head g in lanes ``u*hd:(u+1)*hd``
    and zeros elsewhere, so it scores the tokens ``t`` with ``t % r == u``.
    Each (head, u, g) keeps its own online softmax in its output block,
    resident in VMEM across the row's grid steps (flash-decode, masked per
    position: K and V past the row's length are zeroed, its scores
    dropped); the r partial softmaxes of a query head are merged after
    the kernel, as split-K flash-decode does;
  * ``ppb`` follows from the shapes alone: the largest power of two not
    above the table width whose double-buffered K and V blocks fit
    ``KV_VMEM_BUDGET``;
  * ``row_seg`` (optional) maps each row to its block-table row, so rows
    can share one table row (speculative verify, kernels/spec_verify.py).

GQA: the G = H/Hkv query heads of a kv group are rows of their head's
score tile; MHA is G = 1.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128

# VMEM for the double-buffered K and V blocks: v5e's 16 MiB scoped-VMEM
# default, less the q and output tiles, the softmax scratch and the
# compiler's temporaries for one loaded K and V block.
KV_VMEM_BUDGET = 8 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tokens_per_row(page: int, head_dim: int) -> int:
    """Tokens per lane-dense row of the kernel's page view."""
    if head_dim < LANES and LANES % head_dim == 0 \
            and page % (LANES // head_dim) == 0:
        return LANES // head_dim
    return 1


def pages_per_block(max_pages: int, kv_heads: int, page: int, head_dim: int,
                    dtype) -> int:
    """Pages gathered per grid step: the largest power of two not above
    ``max_pages`` whose K and V blocks, double-buffered, fit the budget.
    A page's VMEM tile pads its rows to the dtype's sublane tile and its
    lanes to 128."""
    r = _tokens_per_row(page, head_dim)
    item = jnp.dtype(dtype).itemsize
    page_bytes = (kv_heads * _round_up(page // r, 32 // item)
                  * _round_up(r * head_dim, LANES) * item)
    ppb = 1
    while ppb * 2 <= max_pages and 2 * 2 * (ppb * 2) * page_bytes \
            <= KV_VMEM_BUDGET:
        ppb *= 2
    return ppb


def _kernel(
    # --- scalar prefetch ---
    seg_ref,             # (B,) int32: row -> block-table row
    tables_ref,          # (S * maxp,) int32, the block table flattened
    lens_ref,            # (B,) int32
    before_ref,          # (B,) int32: blocks of all earlier rows
    next_ref,            # (B,) int32: next row with a block, else B
    # --- operands ---
    q_ref,               # (Hkv, r*G, r*hd) VMEM block
    k_hbm,               # (P, Hkv, page/r, r*hd) in HBM
    v_hbm,
    # --- outputs: each (head, u, g)'s softmax state ---
    acc_ref,             # (Hkv, r*G, r*hd) f32
    m_ref,               # (Hkv, r*G, 1) f32
    l_ref,               # (Hkv, r*G, 1) f32
    # --- scratch ---
    k_buf,               # (2, Hkv, ppb*page/r, r*hd)
    v_buf,
    sems,                # DMA (2, 2): (k|v, slot)
    *, page: int, r: int, g: int, ppb: int, max_pages: int, n_rows: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    hd = k_hbm.shape[-1] // r
    page_rows = page // r

    def row_pages(row):
        return jnp.minimum((lens_ref[row] + page - 1) // page, max_pages)

    def page_copies(row, blk, slot, fn):
        """Apply ``fn`` to the K and V copy of each of ``row``'s own pages
        in block ``blk`` (into buffer ``slot``)."""
        base = seg_ref[row] * max_pages + blk * ppb
        n = jnp.minimum(row_pages(row) - blk * ppb, ppb)

        def one(j, carry):
            src = tables_ref[base + j]
            dst = pl.ds(pl.multiple_of(j * page_rows, page_rows), page_rows)
            fn(pltpu.make_async_copy(k_hbm.at[src], k_buf.at[slot, :, dst],
                                     sems.at[0, slot]))
            fn(pltpu.make_async_copy(v_hbm.at[src], v_buf.at[slot, :, dst],
                                     sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    n_row_blocks = (row_pages(b) + ppb - 1) // ppb

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_row_blocks)
    def _block():
        slot = (before_ref[b] + i) % 2

        @pl.when((before_ref[b] == 0) & (i == 0))
        def _first():                     # nothing before it prefetched it
            page_copies(b, i, slot, lambda c: c.start())

        more = i + 1 < n_row_blocks
        nxt = next_ref[b]

        @pl.when(more | (nxt < n_rows))
        def _prefetch():
            page_copies(jnp.where(more, b, nxt), jnp.where(more, i + 1, 0),
                        1 - slot, lambda c: c.start())

        page_copies(b, i, slot, lambda c: c.wait())

        # tokens of this block still inside the row
        left = lens_ref[b] - i * (ppb * page)

        def slot_of(idx, step):           # u of a query row or a lane
            return sum((idx >= u * step).astype(jnp.int32)
                       for u in range(1, r))

        # the buffer past the row's own tokens holds the unwritten tail of
        # its last page, or stale or unwritten VMEM: zero it, so that
        # 0 * NaN cannot reach a score or the accumulator
        kv_shape = k_buf.shape[1:]                     # (Hkv, rows, r*hd)
        kv_tok = (jax.lax.broadcasted_iota(jnp.int32, kv_shape, 1) * r
                  + slot_of(jax.lax.broadcasted_iota(jnp.int32, kv_shape, 2),
                            hd))
        kv_valid = kv_tok < left
        q = q_ref[...].astype(jnp.float32)             # (Hkv, rG, r*hd)
        k = jnp.where(kv_valid, k_buf[slot].astype(jnp.float32), 0.0)
        v = jnp.where(kv_valid, v_buf[slot].astype(jnp.float32), 0.0)

        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(hd))                  # (Hkv, rG, rows)
        u_q = slot_of(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), g)
        tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) * r + u_q
        valid = tok < left
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                            # (Hkv, rG, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           row_seg=None, *, interpret: bool = False,
                           name: str = "paged_decode_attention"):
    """q: (B, H, hd); k/v_pages: (P, Hkv, page, hd) head-major pages;
    block_tables: (S, maxp) int32 (pad with 0); lengths: (B,) int32;
    row_seg: (B,) int32 row -> block-table row in [0, S), or None for
    row b reading table row b (S = B).  Returns (B, H, hd)."""
    b, h, hd = q.shape
    n_pool, hkv, page, _ = k_pages.shape
    g = h // hkv
    maxp = block_tables.shape[1]
    r = _tokens_per_row(page, hd)
    ppb = pages_per_block(maxp, hkv, page, hd, k_pages.dtype)
    rows = jnp.arange(b, dtype=jnp.int32)
    if row_seg is None:
        row_seg = rows
    lengths = lengths.astype(jnp.int32)
    blocks = (jnp.minimum((lengths + page - 1) // page, maxp) + ppb - 1) \
        // ppb
    before = jnp.cumsum(blocks) - blocks
    with_blocks = jnp.where(blocks > 0, rows, b)
    next_row = jax.lax.cummin(
        jnp.concatenate([with_blocks[1:], jnp.full((1,), b, jnp.int32)]),
        reverse=True)

    # query row (u, g) holds head g in lanes u*hd:(u+1)*hd, zeros elsewhere
    q4 = q.reshape(b, hkv, g, hd)
    eye = jnp.eye(r, dtype=bool)[:, None, :, None]           # (r,1,r,1)
    qw = jnp.where(eye, q4[:, :, None, :, None, :], 0).reshape(
        b, hkv, r * g, r * hd)

    # The TPU compiler cannot slice a page out of an HBM array narrower
    # than 128 lanes, so the kernel reads a lane-dense copy of the pages.
    # Their row-major layout is pinned first: left free, XLA lays the whole
    # pool out anew around that reshape, at one decode row in a layout 8x
    # the pool's size.  The copy moves every page of the pool on every
    # launch, whatever the rows' lengths; a pool stored lane-dense would
    # need none.
    dense = [with_layout_constraint(x, Layout((0, 1, 2, 3))).reshape(
        n_pool, hkv, page // r, r * hd) for x in (k_pages, v_pages)]

    def row_map(bi, ii, *_):
        return (bi, 0, 0, 0)

    def state(width):
        return pl.BlockSpec((None, hkv, r * g, width), row_map)

    acc, m, l = pl.pallas_call(
        functools.partial(_kernel, page=page, r=r, g=g, ppb=ppb,
                          max_pages=maxp, n_rows=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, pl.cdiv(maxp, ppb)),
            in_specs=[
                state(r * hd),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[state(r * hd), state(1), state(1)],
            scratch_shapes=[
                pltpu.VMEM((2, hkv, ppb * page // r, r * hd), k_pages.dtype),
                pltpu.VMEM((2, hkv, ppb * page // r, r * hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, r * g, r * hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, r * g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, r * g, 1), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(row_seg.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      lengths, before, next_row, qw, *dense)

    # merge the r partial softmaxes of each query head
    m = m.reshape(b, hkv, r, g, 1)
    l = l.reshape(b, hkv, r, g, 1)
    acc = acc.reshape(b, hkv, r, g, r, hd)
    acc = jnp.stack([acc[:, :, u, :, u] for u in range(r)], axis=2)
    w = jnp.exp(m - m.max(axis=2, keepdims=True))
    o = (acc * w).sum(axis=2) / jnp.maximum((l * w).sum(axis=2), 1e-30)
    return o.reshape(b, h, hd).astype(q.dtype)
