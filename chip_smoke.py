#!/usr/bin/env python3
"""Bring-up check: serve Qwen1.5-0.5B at its published widths on a TPU.

    python chip_smoke.py               # one chip: two replicas
    python chip_smoke.py --chips 4     # four replicas, one per chip

The model is configs/qwen1_5_0_5b.py at full width (24 layers, d_model
1024, 16 heads of 64, vocab 151936) with random float32 weights drawn
from ``--seed``; no checkpoint is read.  Requests take the normal path:
``ServiceFrontend`` admission, GoRouting dispatch, one ``EngineDriver``
thread per replica, SlideBatching, the paged KV pool and the Pallas
kernels, all in this one process.

One chip (the default) runs, in order:

1. the device check: exits 2 unless JAX's backend is a TPU;
2. the kernel check: lowers ``prefill_packed``, ``decode_step`` and
   ``verify_step`` and fails unless each contains ``tpu_custom_call``;
3. the logit check: prefill plus one decode step through the engine's
   jitted path against ``models.model.forward`` on the same chip;
4. the serving check: two replicas behind GoRouting replay a seeded
   ``industrial`` trace of three priorities; every request must complete,
   with no failed transfer, no failed estimator refit and no driver error.

``--chips 4`` runs only the placement check: four replicas, each pinned
to its own device, replay the trace; then one replica replays it alone.
Every replica's arrays must sit on its own device, every device must
step, and completions and output lengths must match the one-replica run.

The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when it is set and
``<checkout>/.jax_cache`` otherwise.  The last line of standard output is
the JSON result; it is printed only when every check passed.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

BLOCK_SIZE = 16
NUM_BLOCKS = 512          # 1.5 GiB of f32 KV per replica at full width
N_REQUESTS = 36
MAX_IN, MAX_OUT = 320, 32
# the trace's 3.3 s of arrivals replayed in about 70 ms: a burst, so the
# router sees queued work on every replica while it dispatches
REPLAY_SPEED = 50.0
REPLAY_TIMEOUT_S = 600.0
# The logit check runs both paths with every XLA matmul at HIGHEST
# precision, i.e. in float32: the TPU's default runs a float32 matmul as
# one bfloat16 pass (unit roundoff 2**-9), which through 24 layers moved
# the logits by 2.5% of their largest value on a v5e, too much to tell a
# rounding drift from a paging error.  In float32 the engine's paged
# Pallas attention and the reference's dense attention differ only in
# summation order (about 1e-6 relative per op); 1e-3 of the largest
# logit leaves a wide margin for that, while a wrong page, slot or
# position moves a logit by a share of its own size.
LOGIT_TOL_REL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) and the
    seconds they took, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compiles: {self.count} in {self.seconds:.1f} s "
                f"({self.cache_hits} persistent-cache hits)")


# --------------------------------------------------------------------------
# kernel and logit checks
# --------------------------------------------------------------------------

def kernel_and_logit_check(cfg, params, seed: int) -> float:
    """Lower the three serving steps (each must hold a Mosaic kernel), then
    compare prefill + one decode step of the engine's jitted path with
    the dense full-sequence reference.  Returns max |diff| / max |ref| in
    float32."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models.model import forward
    from repro.serving import PagedKVPool, model_exec

    check(not ops._interpret_default(),
          "kernels would run in interpret mode on this backend")
    rng = np.random.default_rng(seed)
    n = 200
    prompt = rng.integers(1, cfg.vocab, n).astype(np.int32)

    def prefill_inputs():
        """A fresh pool holding one request's blocks, and the engine's
        packed-prefill operands for its whole prompt."""
        pool = PagedKVPool(cfg, num_blocks=32, block_size=BLOCK_SIZE)
        pool.ensure_capacity(0, n + 1)
        arrays, smax, sq = model_exec.pack_prefill(
            [(prompt, 0, pool.tables[0])], BLOCK_SIZE)
        table = pool.table_array(
            [0], maxp=model_exec.table_bucket(len(pool.tables[0])))
        return pool, [jnp.asarray(a) for a in arrays], smax, sq, table

    pool, arrays, smax, sq, table = prefill_inputs()
    one = jnp.zeros((1,), jnp.int32)
    lowered = {
        "prefill_packed": model_exec.prefill_packed.lower(
            cfg, params, pool.kv, *arrays, smax, sq),
        "decode_step": model_exec.decode_step.lower(
            cfg, params, pool.kv, one, table, one),
        "verify_step": model_exec.verify_step.lower(
            cfg, params, pool.kv, one, table, one, one)}
    for name, low in lowered.items():
        has = "tpu_custom_call" in low.as_text()
        log(f"lowered {name}: tpu_custom_call={has}")
        check(has, f"lowered {name} holds no tpu_custom_call")

    def engine_logits():
        """Prefill the prompt, then decode its greedy next token, through
        the engine's jitted steps: returns (next token, (2, V) logits)."""
        pool, arrays, smax, sq, table = prefill_inputs()
        logits_p, pool.kv = model_exec.prefill_packed(
            cfg, params, pool.kv, *arrays, smax, sq)
        nxt = int(jnp.argmax(logits_p[0]))
        logits_d, pool.kv = model_exec.decode_batch(
            cfg, params, pool.kv, jnp.asarray([nxt], jnp.int32), table,
            jnp.asarray([n], jnp.int32))
        return nxt, np.stack([np.asarray(logits_p[0]),
                              np.asarray(logits_d[0])])

    ref_fn = jax.jit(forward, static_argnums=0)
    rel = {}
    for precision in ("highest", "default"):
        with jax.default_matmul_precision(precision):
            nxt, got = engine_logits()
            seq = jnp.asarray(np.append(prompt, nxt)[None])
            ref = np.asarray(ref_fn(cfg, params, seq)[0][0, -2:], np.float32)
        diff = float(np.abs(got - ref).max())
        scale = float(np.abs(ref).max())
        rel[precision] = diff / scale
        log(f"logit check, matmul precision {precision} (prefill {n} "
            f"tokens + 1 decode step vs models.model.forward, f32 "
            f"weights): max|diff|={diff:.6g} max|ref|={scale:.6g} "
            f"rel={rel[precision]:.6g}")
    check(rel["highest"] <= LOGIT_TOL_REL,
          f"logit check failed: rel diff {rel['highest']:.6g} > "
          f"{LOGIT_TOL_REL}")
    return rel["highest"]


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def make_trace(seed: int):
    from repro.core import SLO
    from repro.sim import clip_lengths
    from repro.sim.workloads import industrial
    trace = industrial(rate=16.0, duration=4.0, seed=seed)[:N_REQUESTS]
    check(len(trace) == N_REQUESTS, f"trace has {len(trace)} requests")
    return clip_lengths(trace, max_in=MAX_IN, max_out=MAX_OUT,
                        slo=SLO(ttft=2.0, tpot=0.1))


def make_engine(cfg, params, device=None):
    from repro.core import EngineConfig, make_policy
    from repro.serving import Engine
    return Engine(cfg, params, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                  make_policy("slidebatching"), num_blocks=NUM_BLOCKS,
                  block_size=BLOCK_SIZE, device=device)


async def _serve(engines, trace, vocab: int, seed: int):
    from repro.core import GoRouting, RouterConfig
    from repro.core.estimator import BatchLatencyEstimator
    from repro.serving import FrontendConfig, ServiceFrontend
    from repro.sim import replay_frontend

    est = BatchLatencyEstimator(a_p=1e-8, b_p=1e-8, c_p=1e-4, a_d=1e-8,
                                b_d=1e-3, t_c=1e-2)
    fe = ServiceFrontend(GoRouting(est, RouterConfig(pd_mode="coloc")), est,
                         FrontendConfig(max_inflight=4 * len(trace)))
    for eng in engines:
        fe.add_instance(eng)
    await fe.start()
    try:
        report = await asyncio.wait_for(
            replay_frontend(fe, trace, vocab, speed=REPLAY_SPEED, seed=seed,
                            w_p=4.0),
            REPLAY_TIMEOUT_S)
    finally:
        await fe.stop()
    for eng in engines:
        eng.flush_transfers()
    return fe, report


def serve(engines, trace, vocab: int, seed: int):
    """Replay ``trace`` through a frontend over ``engines``; check that
    every request completed and no failure was hidden.  Returns the
    frontend and the replay report."""
    t0 = time.monotonic()
    fe, report = asyncio.run(_serve(engines, trace, vocab, seed))
    log(f"replay: {report.n_completed}/{report.n_submitted} completed, "
        f"{report.n_rejected} rejected, {time.monotonic() - t0:.1f} s wall")
    for p in sorted({s.request.priority for s in report.streams}):
        sub = [s for s in report.streams if s.request.priority == p]
        log(f"  priority {p}: {sum(s.complete for s in sub)}/{len(sub)} "
            f"completed")
    for iid, eng in fe.engines.items():
        st = eng.stats
        dev = eng.device if eng.device is not None else "default"
        log(f"  replica {iid} on {dev}: tokens_out={st.tokens_out} "
            f"iterations={st.iterations} host_syncs={st.host_syncs} "
            f"transfer_failures={st.transfer_failures} "
            f"refit_failures={st.refit_failures}")
    check(not fe.driver_errors,
          f"engine driver threads raised: {fe.driver_errors!r}")
    check(report.n_rejected == 0 and report.n_submitted == len(trace),
          "requests were rejected at admission")
    check(all(s.complete for s in report.streams),
          "not every request completed")
    for iid, eng in fe.engines.items():
        check(eng.stats.transfer_failures == 0,
              f"replica {iid}: transfer failures")
        check(eng.stats.refit_failures == 0, f"replica {iid}: refit failures")
    return fe, report


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def one_chip(cfg, params, args, compiles: CompileCounter) -> None:
    import jax

    t0 = time.monotonic()
    kernel_and_logit_check(cfg, params, args.seed)
    log(f"kernel + logit check: {time.monotonic() - t0:.1f} s; "
        f"{compiles.line()}")
    trace = make_trace(args.seed)
    engines = [make_engine(cfg, params) for _ in range(2)]
    serve(engines, trace, cfg.vocab, args.seed)
    log(compiles.line())
    log(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


def _committed_to(arr, device) -> bool:
    return arr.committed and arr.devices() == {device}


def four_chips(cfg, params, args, compiles: CompileCounter) -> None:
    import jax

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 needs 4 devices, "
                             f"found {len(jax.devices())}")
    trace = make_trace(args.seed)

    engines = [make_engine(cfg, params, device=d) for d in devices]
    for i, (eng, d) in enumerate(zip(engines, devices)):
        leaves = jax.tree.leaves(eng.params) + [eng.pool.kv]
        log(f"replica {i}: device id {d.id} ({d.device_kind})")
        check(all(_committed_to(a, d) for a in leaves),
              f"replica {i}: params or pool not committed to device {d.id}")
    _, rep4 = serve(engines, trace, cfg.vocab, args.seed)
    for i, (eng, d) in enumerate(zip(engines, devices)):
        check(_committed_to(eng.pool.kv, d),
              f"replica {i}: pool left device {d.id} while serving")
        check(eng.stats.iterations > 0, f"device {d.id} ran no step")
    log(compiles.line())
    peaks = [peak_bytes(d) for d in devices]
    del engines
    gc.collect()

    _, rep1 = serve([make_engine(cfg, params, device=devices[0])], trace,
                    cfg.vocab, args.seed)
    log(compiles.line())
    lens4 = [len(s.tokens) for s in rep4.streams]
    lens1 = [len(s.tokens) for s in rep1.streams]
    check(lens4 == lens1, "output lengths differ from the one-replica run")
    same = sum(int(a == b) for s4, s1 in zip(rep4.streams, rep1.streams)
               for a, b in zip(s4.tokens, s1.tokens))
    total = sum(lens1)
    log(f"four replicas vs one: {rep4.n_completed} vs {rep1.n_completed} "
        f"completed, output lengths equal, token agreement "
        f"{same}/{total} = {same / total:.4f}")
    log(f"peak_bytes_in_use per device: {peaks}")


def require_tpu():
    """The device check: returns jax when its backend is a TPU, else
    None."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not a TPU",
              file=sys.stderr)
        return None
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing: {e}",
              file=sys.stderr)
        return 2
    cache_dir = use_compile_cache(ROOT)
    jax = require_tpu()
    if jax is None:
        return 2
    from repro.configs.qwen1_5_0_5b import CONFIG
    from repro.models import init_params

    dev = jax.devices()[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(jax.devices())} compile_cache={cache_dir}")
    compiles = CompileCounter()
    t0 = time.monotonic()
    params = init_params(CONFIG, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    log(f"model {CONFIG.name}: {CONFIG.n_layers} layers, d_model "
        f"{CONFIG.d_model}, vocab {CONFIG.vocab}, f32 weights from seed "
        f"{args.seed} in {time.monotonic() - t0:.1f} s")
    try:
        if args.chips == 4:
            four_chips(CONFIG, params, args, compiles)
        else:
            one_chip(CONFIG, params, args, compiles)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
