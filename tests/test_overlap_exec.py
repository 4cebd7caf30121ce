"""Overlapped execution engine: packed multi-request prefill must be
bitwise-equivalent to the per-request path, the async transfer lanes must
preserve exactness through evict→reload→continue, and the adaptive copy
budget must respond to measured transfer throughput."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import EngineConfig, Request, SLO, make_policy
from repro.core.blocks import BlockManager
from repro.core.estimator import BatchLatencyEstimator
from repro.kernels import chunked_prefill_attention, packed_prefill_attention
from repro.models import forward, init_params
from repro.serving import Engine
from repro.serving.kv_pool import PagedKVPool
from repro.serving.transfer import TransferWorker

# real-model end-to-end matrix: runs in the CI slow shard
pytestmark = pytest.mark.slow

CFG = get_smoke("qwen1_5_0_5b")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
RNG = np.random.default_rng(7)


def greedy_reference(prompt, n):
    cur = jnp.asarray(prompt)[None, :]
    out = []
    for _ in range(n):
        logits, _ = forward(CFG, PARAMS, cur)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        cur = jnp.concatenate([cur, jnp.asarray([[nxt]])], axis=1)
    return out


def make_engine(num_blocks=128, *, packed=True, overlap=True, **bm_kwargs):
    return Engine(CFG, PARAMS, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                  make_policy("slidebatching"), num_blocks=num_blocks,
                  block_size=16, max_ctx=256, bm_kwargs=bm_kwargs,
                  packed_prefill=packed, overlap_transfers=overlap)


def submit(eng, plen, out_len, prio=2, prompt=None):
    r = Request(prompt_len=plen, output_len=out_len, arrival=0.0,
                slo=SLO(3600.0, 3600.0), priority=prio)
    if prompt is None:
        prompt = RNG.integers(1, CFG.vocab, plen).astype(np.int32)
    eng.add_request(r, prompt)
    return r, prompt


# ---------------------------------------------------------------------------
# packed prefill
# ---------------------------------------------------------------------------

def test_packed_kernel_bitwise_matches_per_segment():
    """packed_prefill_attention == S independent chunked_prefill calls with
    cache_lens = ctx + sq, bit for bit (same staging, same kv_block)."""
    rng = np.random.default_rng(0)
    s, sq, h, hkv, hd, smax = 3, 8, 4, 2, 16, 64
    q = rng.standard_normal((s, sq, h, hd)).astype(np.float32)
    k = rng.standard_normal((s, hkv, smax, hd)).astype(np.float32)
    v = rng.standard_normal((s, hkv, smax, hd)).astype(np.float32)
    ctx = np.array([0, 16, 40], np.int32)
    packed = np.asarray(packed_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(ctx),
        kv_block=32))
    for i in range(s):
        ref = np.asarray(chunked_prefill_attention(
            jnp.asarray(q[i:i + 1]), jnp.asarray(k[i:i + 1]),
            jnp.asarray(v[i:i + 1]), jnp.asarray(ctx[i:i + 1] + sq),
            kv_block=32))
        assert np.array_equal(packed[i], ref[0]), f"segment {i} diverged"


def test_packed_prefill_tokens_match_per_request_and_reference():
    lens = (24, 40, 17)
    prompts = [RNG.integers(1, CFG.vocab, n).astype(np.int32) for n in lens]
    refs = [greedy_reference(p, 4) for p in prompts]
    outs = {}
    for packed in (True, False):
        eng = make_engine(packed=packed, overlap=False)
        reqs = [submit(eng, n, 4, prompt=p)[0]
                for n, p in zip(lens, prompts)]
        eng.run_until_drained()
        outs[packed] = [eng.outputs[r.rid] for r in reqs]
        assert (eng.stats.packed_prefill_calls > 0) == packed
        for r, ref in zip(reqs, refs):
            assert eng.outputs[r.rid] == ref
    assert outs[True] == outs[False]


def test_packed_prefill_exact_through_preemption():
    """Tiny pool: packed path + eviction/reload/recompute still matches the
    uninterrupted reference token-for-token."""
    eng = make_engine(num_blocks=10, packed=True, overlap=False)
    reqs = [submit(eng, 40, 6) for _ in range(4)]
    refs = {r.rid: greedy_reference(p, 6) for r, p in reqs}
    eng.run_until_drained(max_iters=400)
    assert eng.stats.evictions > 0
    for r, _ in reqs:
        assert eng.outputs[r.rid] == refs[r.rid]


# ---------------------------------------------------------------------------
# async transfer lanes
# ---------------------------------------------------------------------------

def test_overlap_on_off_identical_streams_under_preemption():
    """evict→(async offload)→reload→continue must yield the same tokens
    with the background lanes on and off."""
    prompts = [RNG.integers(1, CFG.vocab, 40).astype(np.int32)
               for _ in range(4)]
    refs = [greedy_reference(p, 6) for p in prompts]
    streams = {}
    for overlap in (True, False):
        eng = make_engine(num_blocks=10, packed=True, overlap=overlap)
        # priority 3 mirrors most eagerly (n_off=2) -> real D2H traffic
        reqs = [submit(eng, 40, 6, prio=3, prompt=p)[0] for p in prompts]
        eng.run_until_drained(max_iters=400)
        assert eng.stats.evictions > 0
        for r, ref in zip(reqs, refs):
            assert eng.outputs[r.rid] == ref
        streams[overlap] = [eng.outputs[r.rid] for r in reqs]
        eng.kill()
    assert streams[True] == streams[False]


def test_async_offload_lands_and_feeds_accounting():
    eng = make_engine(num_blocks=24, packed=True, overlap=True)
    # enough full blocks per request (prio 3: mirror every 2 full blocks)
    reqs = [submit(eng, 48, 3, prio=3) for _ in range(3)]
    eng.run_until_drained(max_iters=400)
    assert eng.flush_transfers()
    for r, _ in reqs:
        assert r.phase.name == "FINISHED"
    assert eng.stats.offload_blocks > 0, "no async D2H transfer completed"
    assert eng.stats.t_block_measured > 0, "measured t_block never fed back"
    eng.kill()


def test_pool_offload_drop_reload_roundtrip_batched():
    """The batched one-fetch offload + staged reload restore identical
    device block contents."""
    pool = PagedKVPool(CFG, num_blocks=8, block_size=4)
    pool.alloc(1, 3)
    rng = np.random.default_rng(1)
    vals = rng.standard_normal(
        (CFG.n_layers, 2, 3, CFG.n_kv_heads, 4, CFG.hd)).astype(np.float32)
    phys = list(pool.tables[1])
    pool.kv = pool.kv.at[:, :, jnp.asarray(phys)].set(jnp.asarray(vals))
    pool.offload_blocks(1, [0, 1, 2])                    # one device fetch
    assert sorted(pool.host[1]) == [0, 1, 2]
    pool.drop_device_blocks(1)
    # stage via the worker lane, then consume
    w = TransferWorker()
    assert w.prefetch(1, 0, [pool.host[1][i] for i in range(3)])
    assert w.flush()
    staged = w.take_staged(1, 0)
    assert staged is not None and staged[0] == 3
    assert pool.reload_from_device(1, staged[1], 3) == 12
    new_phys = list(pool.tables[1])
    got = np.asarray(pool.kv[:, :, jnp.asarray(new_phys)])
    assert np.array_equal(got, np.moveaxis(
        np.stack([pool.host[1][i] for i in range(3)]), 0, 2))
    w.stop()


def test_stale_epoch_staging_discarded():
    w = TransferWorker()
    blk = np.zeros((2, 2, 4, 2, 8), np.float32)
    assert w.prefetch(5, 0, [blk])
    assert w.flush()
    assert w.take_staged(5, 1) is None      # epoch bumped -> stale
    w.stop()


def test_stale_staging_slot_released_without_consumer():
    """A staging job that completes after invalidate() must not pin one of
    the double-buffer slots forever (rid never reloads again)."""
    w = TransferWorker(max_staged=1)
    blk = np.zeros((2, 2, 4, 2, 8), np.float32)
    assert w.prefetch(5, 0, [blk])
    assert w.flush()
    w.discard_stale(5, current_epoch=1)     # what _drain_transfers does
    assert w.take_staged(5, 1) is None
    assert w.prefetch(6, 0, [blk])          # slot is free again
    assert w.flush()
    # a current-epoch buffer is NOT discarded
    w.discard_stale(6, current_epoch=0)
    assert w.take_staged(6, 0) is not None
    w.stop()


def test_failed_transfer_reported_and_pending_released():
    """A raising copy job must surface as a failed completion (engine
    counts it and releases the BlockManager pending-offload claim)."""
    w = TransferWorker()
    assert w.prefetch(7, 0, [np.zeros(3), np.zeros(2)])  # np.stack raises
    assert w.flush()
    done = w.drain()
    assert len(done) == 1 and not done[0].ok and done[0].n_blocks == 2
    w.stop()
    bm = BlockManager(64, 16, 1e-3)
    bm.external_lanes = True
    bm.offload_sink = lambda *a: None
    r = Request(prompt_len=64, output_len=4, arrival=0.0,
                slo=SLO(10.0, 1.0), priority=3)
    assert bm.grow(r, 64, now=0.0)
    s = bm.state(r)
    assert s.pending_offload == 4
    bm.note_offload_failed(r.rid, 4)
    assert s.pending_offload == 0 and s.mirrored_blocks == 0


def test_staged_reload_hit_end_to_end():
    """The double-buffered reload lane must actually fire: evict a request
    whose blocks were async-mirrored, let the worker pre-stage them, and
    the next reload must consume the staged buffer (a staged HIT) while
    the tokens stay exact."""
    from repro.core.batching import BatchPlan

    eng = make_engine(num_blocks=64, packed=True, overlap=True)
    a, pa = submit(eng, 64, 4, prio=3)      # 4 full blocks, n_off(3)=2
    ref = greedy_reference(pa, 4)
    while a.generated < 1:                  # prefill + first token
        assert eng.step() is not None
        eng.flush_transfers()               # async mirror lands, drained
    assert eng.bm.state(a).mirrored_blocks >= 4
    # preempt A through the real eviction path
    eng.bm.evict(a, eng.now)
    eng._sync_pool_with_bm(BatchPlan(evictions=[a]))
    assert eng.bm.state(a).host_tokens >= 64
    eng._prefetch_reloads()                 # hint the staging lane
    assert eng.flush_transfers()            # staging buffer lands
    eng.run_until_drained(max_iters=100)
    assert eng.stats.staged_hits >= 1, "pre-staged reload never consumed"
    assert eng.outputs[a.rid] == ref
    eng.kill()


# ---------------------------------------------------------------------------
# adaptive copy budget, closed loop
# ---------------------------------------------------------------------------

def test_copy_budget_monotone_in_measured_t_block():
    """Case 2(ii): as the measured per-block copy time grows, the budget
    the engine may spend on reloads must not grow."""
    budgets = []
    for t_block in (1e-4, 5e-4, 2e-3, 8e-3):
        bm = BlockManager(64, 16, t_block)
        budgets.append(bm.copy_budget(t_fwd_min=0.01, t_trans_max=0.08,
                                      t_budget=0.1, b_missing=100))
    assert budgets == sorted(budgets, reverse=True)
    assert budgets[0] > budgets[-1]


def test_observe_transfer_ewma_moves_toward_sample():
    bm = BlockManager(64, 16, 1e-3, t_block_alpha=0.5)
    bm.observe_transfer(4, 4 * 5e-3)        # measured: 5 ms/block
    assert 1e-3 < bm.t_block < 5e-3
    before = bm.t_block
    bm.observe_transfer(4, 4 * 5e-3)
    assert before < bm.t_block < 5e-3       # keeps converging
    assert bm.d2h.t_block == bm.t_block == bm.h2d.t_block
    bm.observe_transfer(0, 1.0)             # degenerate samples ignored
    bm.observe_transfer(4, 0.0)
    assert bm.d2h.t_block == bm.t_block


def test_external_lanes_bypass_virtual_clock():
    bm = BlockManager(64, 16, 1e-3)
    bm.external_lanes = True
    sink_calls = []
    bm.offload_sink = lambda rid, start, n: sink_calls.append(
        (rid, start, n))
    r = Request(prompt_len=64, output_len=4, arrival=0.0,
                slo=SLO(10.0, 1.0), priority=3)
    assert bm.grow(r, 64, now=0.0)          # 4 full blocks, n_off(3)=2
    assert sink_calls == [(r.rid, 0, 4)]
    s = bm.state(r)
    assert s.pending_offload == 4 and s.mirrored_blocks == 0
    bm.complete_offloads(now=1e9)           # virtual clock must NOT fire
    assert s.pending_offload == 4 and s.mirrored_blocks == 0
    bm.note_offload_complete(r.rid, 4)      # the real completion does
    assert s.pending_offload == 0 and s.mirrored_blocks == 4
    bm.note_offload_complete(r.rid, 99)     # over-completion is clamped
    assert s.mirrored_blocks == 4


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_refit_failures_logged_and_counted(monkeypatch):
    eng = make_engine(overlap=False)
    eng.refit_every = 2

    def boom(*a, **k):
        raise RuntimeError("synthetic fit failure")

    monkeypatch.setattr(BatchLatencyEstimator, "fit", boom)
    before = eng.est
    for _ in range(6):
        submit(eng, 16, 2)
    eng.run_until_drained()
    assert eng.stats.refit_failures > 0
    assert eng.est is before                # previous fit kept



def test_seq_cache_tracks_prompt_and_outputs():
    eng = make_engine(overlap=False)
    r, prompt = submit(eng, 20, 3)
    eng.run_until_drained()
    # finished requests are cleaned up
    assert r.rid not in eng._seqs
    # resumed request (failover): prior outputs preload the cache
    eng2 = make_engine(overlap=False)
    r2 = Request(prompt_len=20, output_len=5, arrival=0.0,
                 slo=SLO(3600.0, 3600.0))
    eng2.add_request(r2, prompt, prior_outputs=[3, 4])
    seq = eng2._seq_view(r2)
    assert np.array_equal(seq[:20], prompt) and list(seq[20:]) == [3, 4]


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
