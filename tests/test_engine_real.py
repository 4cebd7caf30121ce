"""Real-engine integration: the paged serving path must be byte-exact with
teacher forcing, including through preemption / offload / reload."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import EngineConfig, Request, SLO, make_policy
from repro.models import forward, init_params
from repro.serving import Engine, ServiceController
from repro.core.gorouting import GoRouting, RouterConfig
from repro.core.estimator import BatchLatencyEstimator

# real-model end-to-end matrix: runs in the CI slow shard
pytestmark = pytest.mark.slow

CFG = get_smoke("qwen1_5_0_5b")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
RNG = np.random.default_rng(0)


def greedy_reference(prompt, n):
    cur = jnp.asarray(prompt)[None, :]
    out = []
    for _ in range(n):
        logits, _ = forward(CFG, PARAMS, cur)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        cur = jnp.concatenate([cur, jnp.asarray([[nxt]])], axis=1)
    return out


def make_engine(policy="slidebatching", num_blocks=128, **bm_kwargs):
    return Engine(CFG, PARAMS, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                  make_policy(policy), num_blocks=num_blocks,
                  block_size=16, max_ctx=256, bm_kwargs=bm_kwargs)


def submit(eng, plen, out_len, prio=1, arrival=0.0):
    r = Request(prompt_len=plen, output_len=out_len, arrival=arrival,
                slo=SLO(3600.0, 3600.0), priority=prio)
    prompt = RNG.integers(1, CFG.vocab, plen).astype(np.int32)
    eng.add_request(r, prompt)
    return r, prompt


def test_engine_matches_greedy_reference():
    eng = make_engine()
    reqs = [submit(eng, int(RNG.integers(8, 40)), 5) for _ in range(3)]
    refs = {r.rid: greedy_reference(p, 5) for r, p in reqs}
    eng.run_until_drained()
    for r, _ in reqs:
        assert eng.outputs[r.rid] == refs[r.rid]


def test_engine_preemption_roundtrip_exact():
    """A tiny pool forces evictions (offload->reload / recompute); outputs
    must STILL match the uninterrupted reference token-for-token."""
    eng = make_engine(num_blocks=10)     # 144 usable tokens < 4*(40+6)
    reqs = [submit(eng, 40, 6) for _ in range(4)]
    refs = {r.rid: greedy_reference(p, 6) for r, p in reqs}
    eng.run_until_drained(max_iters=400)
    assert eng.stats.evictions > 0, "test needs actual preemption pressure"
    for r, _ in reqs:
        assert eng.outputs[r.rid] == refs[r.rid], \
            f"rid {r.rid} diverged after preemption"


def test_engine_sync_vs_async_offload_equivalent_outputs():
    for kwargs in [dict(async_offload=False), dict(recompute_only=True)]:
        eng = make_engine(num_blocks=10, **kwargs)
        reqs = [submit(eng, 40, 4) for _ in range(4)]
        refs = {r.rid: greedy_reference(p, 4) for r, p in reqs}
        eng.run_until_drained(max_iters=400)
        for r, _ in reqs:
            assert eng.outputs[r.rid] == refs[r.rid]


def test_engine_estimator_refit_from_measurements():
    eng = make_engine()
    eng.refit_every = 5
    for _ in range(8):
        submit(eng, 24, 3)
    eng.run_until_drained()
    # after refit, the estimator should predict CPU-scale latencies
    t = eng.est.batch_time([(24, 0, True)])
    assert 1e-4 < t < 60.0


def test_service_failover_completes_all():
    est = BatchLatencyEstimator(a_p=1e-8, b_p=1e-8, c_p=1e-4, a_d=1e-8,
                                b_d=1e-3, t_c=1e-2)
    svc = ServiceController(GoRouting(est, RouterConfig(pd_mode="coloc")),
                            est)
    e0, e1 = make_engine(), make_engine()
    i0 = svc.add_instance(e0)
    i1 = svc.add_instance(e1)
    reqs = []
    for k in range(6):
        r = Request(prompt_len=20, output_len=3, arrival=0.0,
                    slo=SLO(3600.0, 3600.0), priority=1 + k % 2)
        prompt = RNG.integers(1, CFG.vocab, 20).astype(np.int32)
        refs = greedy_reference(prompt, 3)
        svc.submit(r, prompt)
        reqs.append((r, refs))
    svc.step_all()                       # let some work start
    svc.kill_instance(i0)                # hard failure
    svc.serve_until_drained()
    assert len(svc.finished) == 6
    # outputs still correct wherever each request ended up
    eng_by_rid = {}
    for e in svc.engines.values():
        eng_by_rid.update(e.outputs)
    for r, refs in reqs:
        got = eng_by_rid.get(r.rid) or e0.outputs.get(r.rid)
        assert got == refs


def test_service_elastic_add_and_graceful_remove():
    est = BatchLatencyEstimator(c_p=1e-4, b_d=1e-3, t_c=1e-2)
    svc = ServiceController(GoRouting(est, RouterConfig(pd_mode="coloc")),
                            est)
    i0 = svc.add_instance(make_engine())
    for _ in range(4):
        r = Request(prompt_len=16, output_len=2, arrival=0.0,
                    slo=SLO(3600.0, 3600.0))
        svc.submit(r, RNG.integers(1, CFG.vocab, 16).astype(np.int32))
    i1 = svc.add_instance(make_engine())          # scale up
    for _ in range(2):
        r = Request(prompt_len=16, output_len=2, arrival=0.0,
                    slo=SLO(3600.0, 3600.0))
        svc.submit(r, RNG.integers(1, CFG.vocab, 16).astype(np.int32))
    svc.remove_instance(i0, drain=True)           # graceful scale down
    svc.serve_until_drained()
    assert len(svc.finished) == 6


def _committed_to(arr, device) -> bool:
    return arr.committed and arr.devices() == {device}


def test_engine_pinned_to_device_matches_reference():
    """``device=`` commits params and pool to that device, keeps them there
    through preemption and reload, and changes no token."""
    dev = jax.devices()[0]
    eng = Engine(CFG, PARAMS, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                 make_policy("slidebatching"), num_blocks=10, block_size=16,
                 max_ctx=256, device=dev)
    assert all(_committed_to(a, dev) for a in jax.tree.leaves(eng.params))
    assert _committed_to(eng.pool.kv, dev)
    reqs = [submit(eng, 40, 4) for _ in range(4)]
    refs = {r.rid: greedy_reference(p, 4) for r, p in reqs}
    eng.run_until_drained(max_iters=400)
    assert eng.stats.evictions > 0, "test needs actual preemption pressure"
    for r, _ in reqs:
        assert eng.outputs[r.rid] == refs[r.rid]
    assert _committed_to(eng.pool.kv, dev)
    eng.kill()


PINNED_REPLICAS = """
import jax, numpy as np
from repro.configs import get_smoke
from repro.core import EngineConfig, Request, SLO, make_policy
from repro.models import init_params
from repro.serving import Engine

cfg = get_smoke("qwen1_5_0_5b")
params = init_params(cfg, jax.random.PRNGKey(0))
devs = jax.devices()
assert len(devs) == 2
prompts = [np.random.default_rng(i).integers(1, cfg.vocab, 40).astype(np.int32)
           for i in range(4)]
outs = []
for dev in (None, devs[0], devs[1]):
    eng = Engine(cfg, params, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                 make_policy("slidebatching"), num_blocks=10, block_size=16,
                 max_ctx=256, device=dev)
    rids = []
    for p in prompts:
        r = Request(prompt_len=40, output_len=4, arrival=0.0,
                    slo=SLO(3600.0, 3600.0))
        eng.add_request(r, p)
        rids.append(r.rid)
    eng.run_until_drained(max_iters=400)
    assert eng.stats.evictions > 0
    if dev is not None:
        for a in jax.tree.leaves(eng.params) + [eng.pool.kv]:
            assert a.committed and a.devices() == {dev}, (a.devices(), dev)
    outs.append([eng.outputs[r] for r in rids])
    eng.kill()
assert outs[0] == outs[1] == outs[2], outs
print("PINNED_OK")
"""


def test_replicas_pinned_to_separate_devices():
    """Two virtual CPU devices in a subprocess: a replica pinned to the
    second device keeps every array there and streams the same tokens as
    the default-device replica."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", PINNED_REPLICAS], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PINNED_OK" in out.stdout
