"""Async service tier: concurrent streaming over real engine replicas,
admission control, failover, and replay-vs-simulator determinism."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import (EngineConfig, GoRouting, Request, RouterConfig, SLO,
                        make_policy)
from repro.core.estimator import BatchLatencyEstimator
from repro.models import forward, init_params
from repro.serving import (AdmissionError, Engine, FrontendConfig,
                           ServiceFrontend)
from repro.sim import (AnalyticalExecutor, ClusterConfig, ClusterSim,
                       InstanceHardware, QWEN2_7B, clip_lengths, replay_sim)
from repro.sim.workloads import sharegpt

# real-model end-to-end matrix: runs in the CI slow shard
pytestmark = pytest.mark.slow

CFG = get_smoke("qwen1_5_0_5b")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
RNG = np.random.default_rng(0)
SLO_LOOSE = SLO(3600.0, 3600.0)


def make_engine(num_blocks=160):
    return Engine(CFG, PARAMS, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                  make_policy("slidebatching"), num_blocks=num_blocks,
                  block_size=16, max_ctx=256)


def make_frontend(n_replicas=2, **cfg_kwargs):
    est = BatchLatencyEstimator(a_p=1e-8, b_p=1e-8, c_p=1e-4, a_d=1e-8,
                                b_d=1e-3, t_c=1e-2)
    fe = ServiceFrontend(GoRouting(est, RouterConfig(pd_mode="coloc")), est,
                         FrontendConfig(**cfg_kwargs))
    for _ in range(n_replicas):
        fe.add_instance(make_engine())
    return fe


def greedy_reference(prompt, n):
    cur = jnp.asarray(prompt)[None, :]
    out = []
    for _ in range(n):
        logits, _ = forward(CFG, PARAMS, cur)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        cur = jnp.concatenate([cur, jnp.asarray([[nxt]])], axis=1)
    return out


def test_concurrent_streams_across_two_replicas():
    """The acceptance demo: 64 concurrent streaming requests of 2+
    priorities through 2 real engine replicas, measured at the client."""
    async def run():
        fe = make_frontend(n_replicas=2, max_inflight=128)
        await fe.start()
        streams = []
        for k in range(64):
            plen = int(RNG.integers(8, 32))
            r = Request(prompt_len=plen, output_len=3, arrival=0.0,
                        slo=SLO_LOOSE, priority=1 + k % 2,
                        weight=2.0 if k % 2 == 0 else 1.0)
            prompt = RNG.integers(1, CFG.vocab, plen).astype(np.int32)
            streams.append(await fe.submit(r, prompt))
        await asyncio.gather(*[s.collect() for s in streams])
        await fe.stop()
        return fe, streams

    fe, streams = asyncio.run(run())
    assert len(fe.finished) == 64
    for s in streams:
        assert s.done and len(s.tokens) == 3
        assert s.ttft is not None and s.ttft > 0
        assert s.tpot is not None and s.tpot > 0
    # both replicas actually served work
    per_engine = [e.stats.tokens_out for e in fe.engines.values()]
    assert len(per_engine) == 2 and all(t > 0 for t in per_engine)
    assert sum(per_engine) == 64 * 3
    # client-edge per-priority summary is well formed
    from repro.sim import summarize
    summ = summarize(fe.client_edge_requests(), w_p=4.0)
    assert set(summ.per_priority) == {1, 2}
    assert summ.n == 64


def test_stream_ordering_and_event_flags():
    async def run():
        fe = make_frontend(n_replicas=2)
        await fe.start()
        events = {}
        streams = {}
        for k in range(8):
            r = Request(prompt_len=12, output_len=4, arrival=0.0,
                        slo=SLO_LOOSE, priority=1 + k % 2)
            prompt = RNG.integers(1, CFG.vocab, 12).astype(np.int32)
            s = await fe.submit(r, prompt)
            streams[r.rid] = s
            events[r.rid] = []

        async def consume(rid, s):
            async for ev in s:
                assert ev.rid == rid
                events[rid].append(ev)

        await asyncio.gather(*[consume(rid, s)
                               for rid, s in streams.items()])
        await fe.stop()
        return events, streams

    events, streams = asyncio.run(run())
    for rid, evs in events.items():
        # per-stream ordering: 1-based indices strictly increasing
        assert [e.index for e in evs] == list(range(1, 5))
        assert evs[0].first and not any(e.first for e in evs[1:])
        assert evs[-1].last and not any(e.last for e in evs[:-1])
        wall = [e.t_wall for e in evs]
        assert wall == sorted(wall)
        # stream recorded exactly the event tokens
        assert streams[rid].tokens == [e.token for e in evs]


def test_admission_rejection_and_backpressure():
    async def run():
        fe = make_frontend(n_replicas=1,
                           max_inflight=4, priority_quota={1: 1, 2: 2})
        await fe.start()

        def req(prio, out=2):
            return Request(prompt_len=8, output_len=out, arrival=0.0,
                           slo=SLO_LOOSE, priority=prio)

        p8 = RNG.integers(1, CFG.vocab, 8).astype(np.int32)
        s1 = await fe.submit(req(1), p8)
        # priority-1 quota (1) exhausted -> fast rejection...
        with pytest.raises(AdmissionError) as ei:
            await fe.submit(req(1), p8)
        assert ei.value.priority == 1 and ei.value.limit == 1
        # ...but priority 2 has its own quota (isolation)
        s2 = await fe.submit(req(2), p8)
        assert fe.rejected == 1

        # backpressure path: wait=True suspends until the p1 slot frees
        waiter = asyncio.ensure_future(
            fe.submit(req(1), p8, wait=True))
        await asyncio.sleep(0.05)
        assert not waiter.done()          # still blocked on the quota
        await asyncio.gather(s1.collect(), s2.collect())
        s3 = await asyncio.wait_for(waiter, timeout=60.0)
        await s3.collect()
        await fe.drain()
        await fe.stop()
        return fe, s3

    fe, s3 = asyncio.run(run())
    assert len(s3.tokens) == 2
    assert len(fe.finished) == 3


def test_frontend_failover_resumes_streams_exactly():
    """Kill a replica mid-generation: orphans re-dispatch with their
    streamed prefix and every client stream still gets the exact greedy
    reference continuation."""
    async def run():
        fe = make_frontend(n_replicas=2)
        await fe.start()
        cases = []
        for _ in range(6):
            plen = int(RNG.integers(8, 24))
            prompt = RNG.integers(1, CFG.vocab, plen).astype(np.int32)
            r = Request(prompt_len=plen, output_len=8, arrival=0.0,
                        slo=SLO_LOOSE, priority=1)
            s = await fe.submit(r, prompt)
            cases.append((r, prompt, s))
        tasks = [asyncio.ensure_future(s.collect()) for _, _, s in cases]
        # wait until every stream saw its first token, then kill replica 0
        deadline = asyncio.get_running_loop().time() + 120.0
        while any(not s.recv_times for _, _, s in cases):
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.01)
        fe.kill_instance(0)
        await asyncio.gather(*tasks)
        await fe.stop()
        return cases

    cases = asyncio.run(run())
    for r, prompt, s in cases:
        assert len(s.tokens) == 8
        assert s.tokens == greedy_reference(prompt, 8), \
            f"rid {r.rid} diverged across failover"


def make_disagg_frontend(*, coloc=True, **cfg_kwargs):
    """prefill + decode replicas (+ a coloc failover target) behind the
    async frontend, with the role-aware disagg router."""
    est = BatchLatencyEstimator(a_p=1e-8, b_p=1e-8, c_p=1e-4, a_d=1e-8,
                                b_d=1e-3, t_c=1e-2)
    fe = ServiceFrontend(GoRouting(est, RouterConfig(pd_mode="disagg")),
                         est, FrontendConfig(**cfg_kwargs))

    def eng(role):
        return Engine(CFG, PARAMS, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                      make_policy("slidebatching"), num_blocks=160,
                      block_size=16, max_ctx=256, role=role,
                      prefix_cache=False)

    roles = ["prefill", "decode"] + (["coloc"] if coloc else [])
    iids = {role: fe.add_instance(eng(role)) for role in roles}
    return fe, iids


def _disagg_cases(fe, n=4, olen=8):
    async def submit():
        cases = []
        for _ in range(n):
            plen = int(RNG.integers(12, 28))
            prompt = RNG.integers(1, CFG.vocab, plen).astype(np.int32)
            r = Request(prompt_len=plen, output_len=olen, arrival=0.0,
                        slo=SLO_LOOSE, priority=1)
            s = await fe.submit(r, prompt)
            cases.append((r, prompt, s))
        return cases
    return submit


def test_frontend_disagg_two_leg_streams_exact():
    """Happy path through the async frontend: prefill replica -> KV
    handoff -> decode replica, streams measured at the client edge are
    the exact greedy references and the two-leg accounting settles."""
    async def run():
        fe, iids = make_disagg_frontend(coloc=False)
        await fe.start()
        cases = await _disagg_cases(fe)()
        await asyncio.gather(*[s.collect() for _, _, s in cases])
        await fe.stop()
        return fe, iids, cases

    fe, iids, cases = asyncio.run(run())
    for r, prompt, s in cases:
        assert s.tokens == greedy_reference(prompt, 8), \
            f"rid {r.rid} diverged across the handoff"
    book = fe.book
    assert book.handoffs == len(cases)
    assert book.reservation_misses == 0
    assert book.reserved_blocks_total == book.adopted_blocks_total
    assert book.reservations == {}
    for st in book.states.values():
        assert st.reserved_blocks == 0


def test_frontend_churn_decode_replica_dies_mid_handoff():
    """Kill the decode replica once every stream has its first token
    (handoffs in flight or freshly adopted): each request fails over to
    a re-prefill on the coloc replica and the client still receives the
    exact greedy stream — no token lost, none duplicated."""
    async def run():
        fe, iids = make_disagg_frontend()
        await fe.start()
        cases = await _disagg_cases(fe)()
        tasks = [asyncio.ensure_future(s.collect()) for _, _, s in cases]
        deadline = asyncio.get_running_loop().time() + 120.0
        while any(not s.recv_times for _, _, s in cases):
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.005)
        fe.kill_instance(iids["decode"])
        await asyncio.gather(*tasks)
        await fe.stop()
        return fe, cases

    fe, cases = asyncio.run(run())
    assert len(fe.finished) == len(cases)
    for r, prompt, s in cases:
        assert len(s.tokens) == 8          # nothing lost, nothing doubled
        assert s.tokens == greedy_reference(prompt, 8), \
            f"rid {r.rid} diverged across decode-replica death"
    assert fe.book.reservations == {}
    for st in fe.book.states.values():
        assert st.reserved_blocks == 0


def test_frontend_churn_prefill_replica_dies_mid_chunk():
    """Kill the prefill replica right after admission (prompts mid-
    prefill, KV lost): requests re-dispatch to the coloc replica, which
    recomputes and streams the exact references."""
    async def run():
        fe, iids = make_disagg_frontend()
        await fe.start()
        cases = await _disagg_cases(fe)()
        tasks = [asyncio.ensure_future(s.collect()) for _, _, s in cases]
        await asyncio.sleep(0.01)          # let prefill chunks start
        fe.kill_instance(iids["prefill"])
        await asyncio.gather(*tasks)
        await fe.stop()
        return fe, cases

    fe, cases = asyncio.run(run())
    assert len(fe.finished) == len(cases)
    for r, prompt, s in cases:
        assert len(s.tokens) == 8
        assert s.tokens == greedy_reference(prompt, 8), \
            f"rid {r.rid} diverged across prefill-replica death"


def test_replay_sim_deterministic_and_per_priority():
    """The same trace through the cluster simulator is bit-deterministic
    and reports the per-priority gain/SLO split."""
    ex = AnalyticalExecutor(QWEN2_7B, InstanceHardware(chips=4))
    est, mape = ex.fit_estimator(n=200)
    assert mape < 0.15

    def run_once():
        reqs = clip_lengths(sharegpt(rate=30, duration=4, seed=3),
                            max_in=512, max_out=64)
        cs = ClusterSim(lambda: make_policy("slidebatching"),
                        GoRouting(est, RouterConfig(pd_mode="coloc")),
                        ex, est, EngineConfig(w_p=4.0),
                        ClusterConfig(pd_mode="coloc", n_prefill=2))
        return replay_sim(cs, reqs, w_p=4.0)

    a, b = run_once(), run_once()
    row_a = {k: v for k, v in a.row().items() if k != "wall_s"}
    row_b = {k: v for k, v in b.row().items() if k != "wall_s"}
    assert row_a == row_b
    assert a.n_completed == a.n_submitted
    assert set(a.per_priority) == {1, 2}
    for m in a.per_priority.values():
        assert 0.0 <= m["slo"] <= 1.0 and m["tdg_ratio"] >= 0.0


def test_driver_exception_reaches_the_client():
    """A replica whose step raises must not leave its streams waiting
    forever: the error is recorded on the frontend and raised from every
    stream that replica held."""
    async def run():
        fe = make_frontend(n_replicas=1, max_inflight=8)
        eng = next(iter(fe.engines.values()))

        def boom():
            raise ValueError("step exploded")
        eng.step = boom
        await fe.start()
        r = Request(prompt_len=8, output_len=2, arrival=0.0, slo=SLO_LOOSE)
        stream = await fe.submit(r, RNG.integers(1, CFG.vocab, 8))
        with pytest.raises(RuntimeError, match="step exploded"):
            await asyncio.wait_for(stream.collect(), 60)
        assert await fe.drain(timeout=5)
        await fe.stop()
        return fe

    fe = asyncio.run(run())
    (iid, err), = fe.driver_errors.items()
    assert isinstance(err, ValueError)
    assert fe.drivers[iid].error is err
