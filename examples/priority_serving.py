"""End-to-end serving driver: the smoke-scale (2-layer, 64-wide) Qwen1.5
config served with batched multi-priority requests through the full
ProServe stack — SlideBatching, paged KV pool, chunked prefill, paged
flash-decode, priority preemption with host offload/reload — and verified
against uninterrupted greedy generation.  On CPU the Pallas kernels run
in interpret mode; ``chip_smoke.py`` is the full-width run on a TPU.

    PYTHONPATH=src python examples/priority_serving.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax                                                         # noqa: E402
import jax.numpy as jnp                                            # noqa: E402
import numpy as np                                                 # noqa: E402

from repro.configs import get_smoke                                # noqa: E402
from repro.core import EngineConfig, Request, SLO, make_policy     # noqa: E402
from repro.core.tdg import tdg_ratio                               # noqa: E402
from repro.models import forward, init_params                      # noqa: E402
from repro.serving import Engine                                   # noqa: E402


def main():
    cfg = get_smoke("qwen1_5_0_5b")      # reduced qwen1.5 family config
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    # deliberately tiny pool so high-priority arrivals preempt low-priority
    eng = Engine(cfg, params,
                 EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                 make_policy("slidebatching"),
                 num_blocks=20, block_size=16, max_ctx=256)

    reqs = []
    for i in range(8):
        prio = 1 if i % 3 == 0 else 2
        plen = int(rng.integers(16, 48))
        r = Request(prompt_len=plen, output_len=8, arrival=0.0,
                    slo=SLO(ttft=30.0, tpot=10.0), priority=prio,
                    weight=2.0 if prio == 1 else 1.0)
        prompt = rng.integers(1, cfg.vocab, plen).astype(np.int32)
        eng.add_request(r, prompt)
        reqs.append((r, prompt))

    t0 = time.time()
    eng.run_until_drained()
    wall = time.time() - t0

    print(f"served {len(reqs)} multi-priority requests in {wall:.1f}s "
          f"({eng.stats.iterations} iterations, "
          f"{eng.stats.tokens_out} tokens, "
          f"{eng.stats.evictions} preemption evictions, "
          f"{eng.stats.reload_blocks} blocks reloaded)")
    print(f"TDG_Ratio = {tdg_ratio([r for r, _ in reqs], w_p=4.0):.3f}")

    # verify every output against uninterrupted greedy generation
    print("\nverifying against teacher-forced greedy reference...")
    mismatches = 0
    for r, prompt in reqs:
        cur = jnp.asarray(prompt)[None, :]
        ref = []
        for _ in range(r.output_len):
            logits, _ = forward(cfg, params, cur)
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            cur = jnp.concatenate([cur, jnp.asarray([[nxt]])], axis=1)
        ok = eng.outputs[r.rid] == ref
        mismatches += not ok
        print(f"  rid={r.rid} prio={r.priority} "
              f"preemptions={r.preemptions} exact={ok}")
    assert mismatches == 0, "preemption path corrupted generation!"
    print("\nall outputs token-for-token exact through preemption ✓")


if __name__ == "__main__":
    main()
