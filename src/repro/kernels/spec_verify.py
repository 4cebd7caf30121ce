"""Packed speculative-verify attention — scoring k+1 draft positions at once.

Greedy speculative decoding verifies a request's draft chain by running
the decode forward for rows j = 0..depth, where row j processes the
token at position l_kv + j.  All rows of one request share the SAME
block table; materializing a (rows, maxp) table would copy each
request's table depth+1 times and make the scalar-prefetch buffer scale
with the packed row count.

This is ``paged_attention.paged_decode_attention`` itself, given its
optional ``row_seg`` operand: a scalar-prefetched map from each verify
row to its request's row in a compact (S, maxp) block table.  The kernel
body — its (row, page_block) grid, page copies, accumulation order and
masking — is the one decode runs, so every row's output is bitwise-equal
to ``paged_decode_attention`` run with that row's gathered table: the
property the engine's stream-equality guarantee (and
tests/test_spec_decode.py) rests on.

Per-row lengths stay a (rows,) vector: row j of a request passes
l_kv + j + 1, which masks out the same-launch KV writes of rows > j —
causality across the packed rows without any extra masking logic.
"""
from __future__ import annotations

from .paged_attention import paged_decode_attention


def packed_verify_attention(q, k_pages, v_pages, block_tables, lengths,
                            row_seg, *, interpret: bool = False):
    """q: (R, H, hd) — one row per (request, draft position);
    k/v_pages: (P, Hkv, page, hd) head-major pages; block_tables: (S, maxp)
    int32 (pad with 0); lengths: (R,) int32 — per ROW (l_kv + j + 1);
    row_seg: (R,) int32 — row -> block-table row in [0, S).
    Returns (R, H, hd)."""
    return paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                                  row_seg, interpret=interpret,
                                  name="packed_verify_attention")
