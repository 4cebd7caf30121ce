"""Tiered KV cache (hot host tier + int8 cold tier): KVTierStore units,
radix-cache spill/restore/re-adoption, transfer-worker churn under tier
traffic, the engine equivalence matrix (cache on/off x tier on/off), and
the simulator mirror (SimPrefixCache spill + BlockManager host budget)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BlockManager, EngineConfig, Request, SLO,
                        SimPrefixCache, make_policy)
from repro.core.estimator import COLD_WIRE_RATIO, BatchLatencyEstimator
from repro.serving.kv_pool import KVTierStore
from repro.serving.transfer import TransferWorker

# real-model end-to-end matrix: runs in the CI slow shard
pytestmark = pytest.mark.slow

RNG = np.random.default_rng(11)

# synthetic block shape (L, 2, Hkv, bs, hd) — small but full-rank
BSHAPE = (2, 2, 1, 4, 4)


def blk():
    return RNG.standard_normal(BSHAPE).astype(np.float32)


def make_req(plen=100, prio=1, group=-1, shared=0, arrival=0.0):
    return Request(prompt_len=plen, output_len=10, arrival=arrival,
                   slo=SLO(3600.0, 3600.0), priority=prio,
                   prefix_group=group, shared_prefix_len=shared)


# --------------------------------------------------------------------------
# KVTierStore
# --------------------------------------------------------------------------

def test_tier_unbounded_never_demotes():
    tier = KVTierStore(block_bytes=1, budget_bytes=None)
    for rid in range(8):
        tier.put(rid, {0: blk(), 1: blk()})
    assert tier.cold_blocks == 0 and tier.demoted_blocks == 0
    assert tier.hot_blocks == 16


def test_tier_budget_demotes_lru_whole_groups():
    tier = KVTierStore(block_bytes=1, budget_bytes=2, cold_quantize=False)
    tier.put(1, {0: blk(), 1: blk()})
    tier.put(2, {0: blk(), 1: blk()})     # over budget: rid 1 (LRU) demotes
    assert tier.is_cold(1) and not tier.is_cold(2)
    assert tier.hot_blocks == 2 and tier.cold_blocks == 2
    assert tier.host_bytes <= 2
    # whole-group invariant: no rid straddles tiers
    assert not tier.hot.get(1) and not tier.cold.get(2)
    # touching rid 1 (read) makes rid 2 the next victim
    tier.get_block(1, 0)
    tier.put(3, {0: blk()})
    assert tier.is_cold(2)


def test_tier_exact_mode_roundtrip_bitwise():
    tier = KVTierStore(block_bytes=1, budget_bytes=1, cold_quantize=False)
    a = blk()
    tier.put(1, {0: a})
    tier.put(2, {0: blk()})               # demotes rid 1 (raw fp32 cold)
    assert tier.is_cold(1)
    got = tier.get_block(1, 0)
    np.testing.assert_array_equal(got, a)


def test_tier_quantized_roundtrip_error_bound():
    tier = KVTierStore(block_bytes=1, budget_bytes=1, cold_quantize=True)
    a = blk()
    tier.put(1, {0: a})
    tier.put(2, {0: blk()})               # demotes rid 1 via int8 wire
    assert tier.is_cold(1) and tier.demoted_blocks == 1
    got = tier.get_block(1, 0)
    # documented bound (kernels/kv_quant.py): |x - deq| <= scale/2 per
    # element, scale = plane_absmax / 127
    planes = a.reshape(BSHAPE[0] * BSHAPE[1], -1)
    scale = np.abs(planes).max(axis=1) * (1.0 / 127.0)
    err = np.abs(got - a).reshape(BSHAPE[0] * BSHAPE[1], -1).max(axis=1)
    assert np.all(err <= scale * 0.5 + 1e-7)
    assert tier.cold_reload_blocks == 1


def test_tier_promotion_reunites_group_hot():
    tier = KVTierStore(block_bytes=1, budget_bytes=4, cold_quantize=False)
    tier.put(1, {0: blk(), 1: blk()})
    tier.put(2, {0: blk(), 1: blk(), 2: blk()})   # rid 1 demotes
    assert tier.is_cold(1)
    tier.put(1, {2: blk()})               # new hot put promotes the group
    assert not tier.is_cold(1) and tier.n_blocks(1) == 3


def test_tier_split_group_rekeys_lower_half():
    tier = KVTierStore(block_bytes=1, budget_bytes=None)
    blocks = {i: blk() for i in range(4)}
    tier.put(1, dict(blocks))
    tier.split_group(1, 2, new_rid=-5)
    assert sorted(tier.hot[1]) == [0, 1]
    assert sorted(tier.hot[-5]) == [0, 1]      # old 2,3 re-keyed from 0
    np.testing.assert_array_equal(tier.hot[-5][0], blocks[2])
    np.testing.assert_array_equal(tier.hot[-5][1], blocks[3])


def test_tier_prefer_cold_and_payload_kinds():
    tier = KVTierStore(block_bytes=1, budget_bytes=2, cold_quantize=True)
    assert not tier.prefer_cold(2)        # fits the empty budget
    tier.put(1, {0: blk(), 1: blk()})
    assert tier.prefer_cold(1)            # would land demote-bound
    tier.put(2, {0: blk()})               # demotes rid 1
    hot_payloads = tier.payloads(2, [0])
    cold_payloads = tier.payloads(1, [0, 1])
    assert isinstance(hot_payloads[0], np.ndarray)
    assert all(isinstance(p, tuple) for p in cold_payloads)
    assert tier.payloads(1, [0, 7]) is None    # any-missing -> None


# --------------------------------------------------------------------------
# TransferWorker churn under tier traffic (failure paths)
# --------------------------------------------------------------------------

def _host_group(n=2):
    return [blk() for _ in range(n)]


def test_worker_invalidate_races_reload_and_frees_slot():
    w = TransferWorker(max_staged=1)
    try:
        assert w.prefetch(5, 0, _host_group())
        assert w.flush()
        w.invalidate(5)                    # eviction races the staged buffer
        assert w.take_staged(5, 0) is None
        # the slot is free again: a new group can stage immediately
        assert w.prefetch(6, 0, _host_group())
        assert w.flush()
        assert w.take_staged(6, 0) is not None
    finally:
        w.stop()


def test_worker_stale_epoch_completion_discarded():
    """A staging job that lands AFTER the rid's residency epoch moved on
    must not be consumed, and discard_stale must free its ring slot."""
    w = TransferWorker(max_staged=1)
    try:
        assert w.prefetch(5, 0, _host_group())
        assert w.flush()
        assert w.take_staged(5, 1) is None     # epoch bumped: stale
        w.discard_stale(5, 1)                  # reap the dead buffer
        assert w.prefetch(5, 1, _host_group())
        assert w.flush()
        n, arr = w.take_staged(5, 1)
        assert n == 2 and arr.shape[0] == 2
    finally:
        w.stop()


def test_worker_quantized_wire_dequantizes_on_device():
    from repro.kernels.ref import (kv_block_dequantize_ref,
                                   kv_block_quantize_ref)
    group = np.stack(_host_group(3))
    vals, scales = kv_block_quantize_ref(jnp.asarray(group))
    vals, scales = np.asarray(vals), np.asarray(scales)
    payloads = [(vals[i], scales[i]) for i in range(3)]
    w = TransferWorker(max_staged=1)
    try:
        assert w.prefetch(7, 0, payloads)
        assert w.flush()
        done = w.drain()
        assert any(d.kind == "h2d" and d.quantized for d in done)
        n, arr = w.take_staged(7, 0)
        assert n == 3
        want = np.asarray(kv_block_dequantize_ref(
            jnp.asarray(vals), jnp.asarray(scales)))
        np.testing.assert_allclose(np.asarray(arr), want, atol=1e-6)
    finally:
        w.stop()


# --------------------------------------------------------------------------
# RadixPrefixCache spill / restore / re-adoption
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_cfg():
    from repro.configs import get_smoke
    return get_smoke("qwen1_5_0_5b")


@pytest.fixture()
def spill_env(smoke_cfg):
    from repro.serving import PagedKVPool, RadixPrefixCache
    pool = PagedKVPool(smoke_cfg, 32, 16,
                       host_tier_bytes=1 << 30, cold_quantize=False)
    bm = BlockManager(31, 16, 1e-3)
    cache = RadixPrefixCache(pool, bm, max_blocks=16, spill=True)
    return pool, bm, cache


def _prefill(pool, rid, tokens, fill=None):
    assert pool.ensure_capacity(rid, len(tokens))
    if fill is not None:
        for b in pool.tables[rid]:
            pool.kv = pool.kv.at[:, :, b].set(fill)
    return pool.tables[rid]


def test_cache_spill_restore_roundtrip_exact(spill_env):
    pool, bm, cache = spill_env
    toks = RNG.integers(1, 999, 64).astype(np.int32)
    # match with a longer prompt so the whole 4-block node is usable
    # (usable_prefix keeps >=1 prompt token uncached)
    q = np.concatenate([toks, RNG.integers(1, 999, 16)]).astype(np.int32)
    fill = jnp.asarray(RNG.standard_normal(
        pool.kv.shape[:2] + pool.kv.shape[3:]).astype(np.float32))
    _prefill(pool, 1, toks, fill)
    adopted = cache.insert(toks, pool.tables[1], rid=1, now=0.0)
    assert adopted == 4
    bm.charge_cache(adopted)
    cache.detach(1)
    pool.release(1)
    free_before = len(pool.free)
    # eviction SPILLS to the host tier instead of destroying the blocks
    assert cache.reclaim(4) == 4
    assert len(pool.free) == free_before + 4
    assert cache.stats.spilled_blocks == 4
    assert bm.cache_charge == 0
    assert pool.tier.hot_blocks == 4       # parked under a pseudo-rid
    # a later match RESTORES the spilled node (device blocks + charge back)
    n, blocks = cache.match(q, now=1.0, rid=2)
    assert n == 64 and len(blocks) == 4
    assert cache.stats.restored_blocks == 4
    assert bm.cache_charge == 4
    assert pool.tier.hot_blocks == 0       # host copy consumed
    for b in blocks:
        assert bool(jnp.array_equal(pool.kv[:, :, b], fill))


def test_cache_spill_readopt_on_insert(spill_env):
    pool, bm, cache = spill_env
    toks = RNG.integers(1, 999, 64).astype(np.int32)
    q = np.concatenate([toks, RNG.integers(1, 999, 16)]).astype(np.int32)
    _prefill(pool, 1, toks)
    bm.charge_cache(cache.insert(toks, pool.tables[1], rid=1, now=0.0))
    cache.detach(1)
    pool.release(1)
    assert cache.reclaim(4) == 4
    # a new request recomputed the same prompt: insert re-adopts its table
    # blocks and supersedes the host-tier copy (no reload)
    _prefill(pool, 2, toks)
    adopted = cache.insert(toks, pool.tables[2], rid=2, now=2.0)
    assert adopted == 4
    assert cache.stats.readopted_blocks == 4
    assert cache.stats.restored_blocks == 0
    assert pool.tier.hot_blocks == 0       # spill group dropped
    n, _ = cache.match(q, now=3.0, rid=3)
    assert n == 64


def test_cache_restore_pool_full_is_plain_miss(spill_env):
    pool, bm, cache = spill_env
    toks = RNG.integers(1, 999, 64).astype(np.int32)
    q = np.concatenate([toks, RNG.integers(1, 999, 16)]).astype(np.int32)
    _prefill(pool, 1, toks)
    bm.charge_cache(cache.insert(toks, pool.tables[1], rid=1, now=0.0))
    cache.detach(1)
    pool.release(1)
    assert cache.reclaim(4) == 4
    hog = pool._alloc_free_blocks(len(pool.free))     # exhaust the device
    assert not pool.free
    n, blocks = cache.match(q, now=1.0, rid=2)
    assert n == 0 and blocks == []
    # the spilled copy survives for a later, less-pressured match
    assert pool.tier.hot_blocks == 4
    for b in hog:
        pool.decref(b)
    n2, _ = cache.match(q, now=2.0, rid=3)
    assert n2 == 64


def test_cache_readopt_mid_reload_invalidates_staged_buffer(spill_env):
    """Re-adoption while the worker holds a pre-staged H2D buffer for the
    spilled group must invalidate that buffer (it would otherwise pin a
    staging slot for a group that no longer exists)."""
    pool, bm, cache = spill_env
    w = TransferWorker(max_staged=2)
    cache.worker = w
    try:
        toks = RNG.integers(1, 999, 64).astype(np.int32)
        _prefill(pool, 1, toks)
        bm.charge_cache(cache.insert(toks, pool.tables[1], rid=1, now=0.0))
        cache.detach(1)
        pool.release(1)
        assert cache.reclaim(4) == 4
        (host_rid, payloads), = cache.spill_candidates(limit=1)
        assert w.prefetch(host_rid, 0, payloads)
        assert w.flush()
        # mid-reload re-adoption: a request recomputed the same prompt
        _prefill(pool, 2, toks)
        assert cache.insert(toks, pool.tables[2], rid=2, now=2.0) == 4
        assert w.take_staged(host_rid, 0) is None     # buffer invalidated
        assert not cache.has_spilled(host_rid)
    finally:
        w.stop()


def test_cache_spilled_match_can_use_staged_buffer(spill_env):
    pool, bm, cache = spill_env
    w = TransferWorker(max_staged=2)
    cache.worker = w
    try:
        toks = RNG.integers(1, 999, 64).astype(np.int32)
        q = np.concatenate([toks, RNG.integers(1, 999, 16)]).astype(np.int32)
        _prefill(pool, 1, toks)
        bm.charge_cache(cache.insert(toks, pool.tables[1], rid=1, now=0.0))
        cache.detach(1)
        pool.release(1)
        assert cache.reclaim(4) == 4
        (host_rid, payloads), = cache.spill_candidates(limit=1)
        assert w.prefetch(host_rid, 0, payloads)
        assert w.flush()
        n, blocks = cache.match(q, now=1.0, rid=2)
        assert n == 64 and len(blocks) == 4
        assert cache.stats.staged_restores == 1
    finally:
        w.stop()


# --------------------------------------------------------------------------
# Simulator mirror: BlockManager host budget + SimPrefixCache spill
# --------------------------------------------------------------------------

def test_bm_host_budget_demotes_lru_and_scales_reload_wire():
    bm = BlockManager(32, 16, 1e-3, host_budget_blocks=2,
                      n_off_by_priority={1: 2, 2: 2, 3: 2})
    r1 = make_req(plen=32, prio=3)
    r2 = make_req(plen=32, prio=3)
    assert bm.grow(r1, 32, 0.0) and bm.grow(r2, 32, 0.0)
    bm.evict(r1, 1.0)                   # 2 mirrored blocks -> host (hot)
    assert bm.state(r1).host_tokens == 32
    assert bm.state(r1).cold_tokens == 0
    bm.evict(r2, 2.0)                   # over budget: r1 (LRU) demotes
    assert bm.state(r1).cold_tokens == 32
    assert bm.state(r2).cold_tokens == 0
    # cold reload occupies the H2D lane at COLD_WIRE_RATIO width
    plan = bm.plan_reload(r1, 100, 1 << 20, 1 << 20)
    assert plan.restore_blocks == 2
    done = bm.apply_reload(r1, plan, 10.0)
    assert done == pytest.approx(10.0 + 2 * 1e-3 * COLD_WIRE_RATIO)
    plan2 = bm.plan_reload(r2, 100, 1 << 20, 1 << 20)
    done2 = bm.apply_reload(r2, plan2, 20.0)
    assert done2 == pytest.approx(20.0 + 2 * 1e-3)    # hot: full width


def test_estimator_reload_time_tier_pricing():
    est = BatchLatencyEstimator()
    t = 5e-4
    assert est.reload_time(7, 0, t) == 7 * t              # legacy bitwise
    assert est.reload_time(0, 8, t) == pytest.approx(
        COLD_WIRE_RATIO * 8 * t)
    assert est.reload_time(3, 4, t) == pytest.approx((3 + 1.0) * t)


def test_sim_cache_spill_restore_and_cold_wire():
    bm = BlockManager(64, 16, 1e-3)
    cache = SimPrefixCache(16, 32, spill=True, host_budget_blocks=4)
    cache.bm = bm
    bm.cache = cache
    r1 = make_req(plen=100, group=1, shared=64)
    r2 = make_req(plen=100, group=2, shared=64)
    for r in (r1, r2):
        bm.charge_cache(cache.insert(r, now=0.0))
        cache.detach(r.rid)
    assert cache.cached_blocks == 8
    # evictions SPILL whole groups; beyond the 4-block host budget the
    # LRU spilled group (1) demotes to the cold tier
    assert cache.reclaim(8) == 8
    assert bm.cache_charge == 0
    assert set(cache.spilled) == {1, 2}
    assert cache.spilled[1].cold and not cache.spilled[2].cold
    assert cache.spilled_blocks == 8
    # a later match restores group 1 over the NARROW wire
    got = cache.match(make_req(plen=100, group=1, shared=64), now=10.0)
    assert got == 64
    assert cache.restored_blocks == 4
    assert bm.cache_charge == 4
    assert bm.h2d.busy_until == pytest.approx(
        10.0 + 4 * 1e-3 * COLD_WIRE_RATIO)
    # group 2 is still hot: full-width wire
    got2 = cache.match(make_req(plen=100, group=2, shared=64), now=20.0)
    assert got2 == 64
    assert bm.h2d.busy_until == pytest.approx(20.0 + 4 * 1e-3)
    assert not cache.spilled


def test_sim_cache_restore_pool_full_is_miss():
    bm = BlockManager(8, 16, 1e-3)
    cache = SimPrefixCache(16, 8, spill=True)
    cache.bm = bm
    bm.cache = cache
    r1 = make_req(plen=100, group=1, shared=64)
    bm.charge_cache(cache.insert(r1, now=0.0))
    cache.detach(r1.rid)
    assert cache.reclaim(4) == 4
    hog = make_req(plen=128)
    assert bm.grow(hog, 128, 0.0)       # 8 blocks: device full
    assert cache.match(make_req(plen=100, group=1, shared=64), now=1.0) == 0
    assert 1 in cache.spilled           # copy kept for later
    bm.release(hog)
    assert cache.match(make_req(plen=100, group=1, shared=64), now=2.0) == 64


def test_sim_cache_readopt_on_insert():
    bm = BlockManager(64, 16, 1e-3)
    cache = SimPrefixCache(16, 32, spill=True)
    cache.bm = bm
    bm.cache = cache
    r1 = make_req(plen=100, group=1, shared=64)
    bm.charge_cache(cache.insert(r1, now=0.0))
    cache.detach(r1.rid)
    assert cache.reclaim(4) == 4
    # a request that recomputed the prefix re-inserts: spilled copy is
    # superseded without an H2D restore
    r2 = make_req(plen=100, group=1, shared=64)
    adopted = cache.insert(r2, now=5.0)
    assert adopted == 4
    assert 1 not in cache.spilled
    assert bm.h2d.busy_until == 0.0


# --------------------------------------------------------------------------
# Engine end-to-end: cache on/off x tier on/off matrix (exact mode)
# --------------------------------------------------------------------------

def _matrix_engine(smoke_cfg, params, *, prefix_cache, host_tier_bytes,
                   cold_quantize):
    from repro.serving import Engine
    return Engine(smoke_cfg, params, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                  make_policy("slidebatching"), num_blocks=7,
                  block_size=16, max_ctx=256, prefix_cache=prefix_cache,
                  host_tier_bytes=host_tier_bytes,
                  cold_quantize=cold_quantize)


def _matrix_run(smoke_cfg, params, prompts, *, prefix_cache, host_tier_bytes,
                cold_quantize=False):
    eng = _matrix_engine(smoke_cfg, params, prefix_cache=prefix_cache,
                         host_tier_bytes=host_tier_bytes,
                         cold_quantize=cold_quantize)
    reqs = []
    # staged admission: the first request seeds the radix cache before the
    # rest arrive and share its prefix blocks
    for wave in (prompts[:1], prompts[1:]):
        for p in wave:
            r = make_req(plen=len(p))
            r.output_len = 5
            eng.add_request(r, p)
            reqs.append(r)
        eng.run_until_drained(max_iters=400)
    return eng, [eng.outputs[r.rid] for r in reqs]


@pytest.fixture(scope="module")
def smoke_params(smoke_cfg):
    import jax
    from repro.models import init_params
    return init_params(smoke_cfg, jax.random.PRNGKey(0))


def test_engine_tier_matrix_exact_mode_bitwise(smoke_cfg, smoke_params):
    """Exact mode (fp32 cold tier): every cache x tier combination must
    emit the uninterrupted greedy reference token-for-token.  The tiny
    pool forces evictions, so tiered runs exercise spill + demote +
    reload on the live token path."""
    from repro.models import forward

    rng = np.random.default_rng(31)
    shared = rng.integers(1, smoke_cfg.vocab, 32).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(1, smoke_cfg.vocab, 8 + 4 * i)
                               .astype(np.int32)]) for i in range(4)]

    def ref(prompt, n=5):
        import jax.numpy as jnp
        cur = jnp.asarray(prompt)[None, :]
        out = []
        for _ in range(n):
            logits, _ = forward(smoke_cfg, smoke_params, cur)
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            cur = jnp.concatenate([cur, jnp.asarray([[nxt]])], axis=1)
        return out

    refs = [ref(p) for p in prompts]
    probe = _matrix_engine(smoke_cfg, smoke_params, prefix_cache=False,
                           host_tier_bytes=1 << 30, cold_quantize=False)
    bb = probe.pool.tier.block_bytes
    tier_demoted = 0
    for cache_on in (False, True):
        for tier_bytes in (None, 2 * bb):
            eng, outs = _matrix_run(smoke_cfg, smoke_params, prompts,
                                    prefix_cache=cache_on,
                                    host_tier_bytes=tier_bytes)
            assert outs == refs, (
                f"diverged: cache={cache_on} tier={tier_bytes}")
            if tier_bytes is not None:
                assert eng.stats.evictions > 0
                tier_demoted += eng.pool.tier.demoted_blocks
    # at least one tiered run must have pushed past the 2-block host
    # budget into the (exact fp32) cold tier
    assert tier_demoted > 0


def test_engine_tier_int8_cold_completes_under_pressure(smoke_cfg,
                                                        smoke_params):
    """Quantized cold tier: the engine must complete every request through
    int8 demote/reload cycles (no bitwise claim — int8 is lossy)."""
    rng = np.random.default_rng(32)
    shared = rng.integers(1, smoke_cfg.vocab, 32).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(1, smoke_cfg.vocab, 8 + 4 * i)
                               .astype(np.int32)]) for i in range(4)]
    probe = _matrix_engine(smoke_cfg, smoke_params, prefix_cache=False,
                           host_tier_bytes=1 << 30, cold_quantize=True)
    bb = probe.pool.tier.block_bytes
    eng, outs = _matrix_run(smoke_cfg, smoke_params, prompts,
                            prefix_cache=True, host_tier_bytes=2 * bb,
                            cold_quantize=True)
    assert all(len(o) == 5 for o in outs)
    assert eng.stats.spill_blocks > 0
    # demote-bound traffic lands cold either by direct int8 offload
    # (prefer_cold) or by later LRU demotion
    assert eng.stats.cold_blocks + eng.pool.tier.demoted_blocks > 0
    assert eng.stats.host_bytes <= 2 * bb
