"""KV-block gather: compact scattered pool pages into a contiguous staging
buffer.

The §4.3 block manager offloads/reloads pages between device and host; the
host DMA engine wants CONTIGUOUS device buffers, while the paged pool
scatters a request's pages arbitrarily.  This kernel gathers the pages
named by ``indices`` into a staging buffer in one pass (index-driven
BlockSpec = one DMA per page, no compute) — the device half of the
asynchronous offloading path.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, pool_ref, out_ref):
    del idx_ref
    out_ref[...] = pool_ref[...]


def block_gather(pool, indices, *, interpret: bool = False):
    """pool: (P, *page_shape), e.g. head-major ``(P, Hkv, page, hd)``;
    indices: (n,) int32 -> (n, *page_shape).  The block is one whole page,
    so it tiles for any page shape."""
    n = indices.shape[0]
    page_shape = pool.shape[1:]
    rest = (0,) * len(page_shape)

    def in_map(i, idx):
        return (idx[i],) + rest

    def out_map(i, idx):
        return (i,) + rest

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((1,) + page_shape, in_map)],
            out_specs=pl.BlockSpec((1,) + page_shape, out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((n,) + page_shape, pool.dtype),
        interpret=interpret,
    )(indices, pool)
