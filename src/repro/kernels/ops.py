"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend every kernel compiles with Mosaic (never interpreted):
tests/test_tpu_compile.py compiles each one for a described v5e at
Qwen1.5-0.5B widths.  On any other backend they run in ``interpret=True``
mode (the kernel body executes as traced jnp ops), which is how the CPU
tests validate them against the ref.py oracles.
"""
from __future__ import annotations

from functools import partial

import jax

from .block_gather import block_gather as _block_gather
from .chunked_prefill import chunked_prefill_attention as _chunked_prefill
from .chunked_prefill import packed_prefill_attention as _packed_prefill
from .kv_quant import kv_block_dequantize as _kv_dequant
from .kv_quant import kv_block_quantize as _kv_quant
from .paged_attention import paged_decode_attention as _paged_decode
from .spec_verify import packed_verify_attention as _packed_verify


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           interpret: bool | None = None):
    it = _interpret_default() if interpret is None else interpret
    return _paged_decode(q, k_pages, v_pages, block_tables, lengths,
                         interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def packed_verify_attention(q, k_pages, v_pages, block_tables, lengths,
                            row_seg, interpret: bool | None = None):
    it = _interpret_default() if interpret is None else interpret
    return _packed_verify(q, k_pages, v_pages, block_tables, lengths,
                          row_seg, interpret=it)


@partial(jax.jit, static_argnames=("kv_block", "interpret"))
def chunked_prefill_attention(q, k_cache, v_cache, cache_lens,
                              kv_block: int = 512,
                              interpret: bool | None = None):
    it = _interpret_default() if interpret is None else interpret
    return _chunked_prefill(q, k_cache, v_cache, cache_lens,
                            kv_block=kv_block, interpret=it)


@partial(jax.jit, static_argnames=("kv_block", "interpret"))
def packed_prefill_attention(q, k_cache, v_cache, ctx_lens,
                             kv_block: int = 512,
                             interpret: bool | None = None):
    it = _interpret_default() if interpret is None else interpret
    return _packed_prefill(q, k_cache, v_cache, ctx_lens,
                           kv_block=kv_block, interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def block_gather(pool, indices, interpret: bool | None = None):
    it = _interpret_default() if interpret is None else interpret
    return _block_gather(pool, indices, interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def kv_block_quantize(blocks, interpret: bool | None = None):
    it = _interpret_default() if interpret is None else interpret
    return _kv_quant(blocks, interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def kv_block_dequantize(vals, scales, interpret: bool | None = None):
    it = _interpret_default() if interpret is None else interpret
    return _kv_dequant(vals, scales, interpret=it)
