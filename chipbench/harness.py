"""One run of one cell: build, warm up, ramp the load, measure, check.

The system under test is the program's serving stack as a user drives it:
``ServiceFrontend.submit`` -> GoRouting -> ``EngineDriver`` thread ->
``Engine.step`` (SlideBatching, block manager, paged pool, prefix cache) ->
``model_exec.prefill_packed`` / ``decode_step`` -> the Pallas kernels.
The harness makes the weights, the requests and the reference itself.

Timeline of a run (all on ``time.monotonic()``):

1. set-up: weights from the seed on the device, the engine and frontend,
   a burst of the cell's own warm-up requests (a fixed seed, outputs cut
   to ``warm_output``) so their shapes compile or load from the cache;
2. ramp: the open-loop generator starts ``ramp_s`` before the window, so
   the window opens on a running system (the ramp counts as set-up);
3. window: ``--seconds`` of measurement; every request due in it counts;
4. close: device memory peak read; the engine keeps serving what it has,
   with nothing new sent, until the finished requests hold the check's
   sample (at most ``DRAIN_S``); then the frontend stops, the engine state
   is freed and the reference checks a sample of the finished requests.
"""
from __future__ import annotations

import asyncio
import copy
import dataclasses
import gc
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import e2e, families, judge, traffic
from .spans import Recorder, instrument
from .spec import ROOT, Cell

WARM_SEED = 12345          # set-up replays the same warm requests each run
DRAIN_S = 60.0             # longest wait after the close for finished requests


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache(jax) -> str:
    """JAX's persistent cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program written to it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """XLA compilations, persistent-cache hits and traces, from JAX's own
    monitoring events, with the count at each mark."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax
        self.n = {"compiles": 0, "traces": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        kind = self.EVENTS.get(event)
        if kind:
            self.n[kind] += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1

    def snap(self) -> dict:
        return dict(self.n)


def require_chip(jax, chips: int) -> None:
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"JAX backend is {backend!r}, not a TPU")
    if len(jax.devices()) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(jax.devices())}")


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file, from its
    family (``families/<model_type>.py``)."""
    return families.load(cfg).arch_config(cfg)


@dataclass
class Outcome:
    """Everything a run measured, for the metrics and the result line."""
    recs: list
    t0: float
    t_open: float
    t_close: float
    rec: Recorder
    stats: list = field(default_factory=list)     # per replica (open, close)
    est: list = field(default_factory=list)       # per replica estimator
    # coefficients (open, close)
    compiles: dict = field(default_factory=dict)  # per phase
    lateness: list = field(default_factory=list)
    driver_errors: dict = field(default_factory=dict)
    memory_peak: int = -1
    warm_s: float = 0.0
    drain_s: float = 0.0
    queue: list = field(default_factory=list)     # (waiting, decoding) at
    # the open and at the close


def _queue(sent) -> tuple:
    """Requests sent that wait for their first token, and that decode."""
    waiting = sum(1 for s in sent if not s.failed and not s.times)
    decoding = sum(1 for s in sent if s.times and not s.finished)
    return waiting, decoding


def _stats(engine) -> dict:
    """A copy of every field of the engine's ``EngineStats``, so that a
    counter the program adds reaches the readers without a change here."""
    s = engine.stats
    return {f.name: copy.deepcopy(getattr(s, f.name))
            for f in dataclasses.fields(s)}


def _changed(a: dict, b: dict) -> str:
    """``name=value`` of each counter that moved between two ``_stats``
    snapshots: the difference of a count, ``old->new`` of anything
    else."""
    out = []
    for k, v in b.items():
        if v == a.get(k):
            continue
        if type(v) is int and type(a.get(k)) is int:
            out.append(f"{k}={v - a[k]}")
        else:
            out.append(f"{k}={a.get(k)!r}->{v!r}")
    return " ".join(out)


async def _consume(stream, sent: e2e.Sent) -> None:
    try:
        async for ev in stream:
            sent.times.append(time.monotonic())
            sent.tokens.append(int(ev.token))
    except Exception as e:            # a driver failure closes the stream
        sent.failed, sent.error = True, repr(e)


async def _serve(cell: Cell, params, arch, reqs, warm, seconds: float,
                 trace: bool, t0: float, counter: CompileCounter,
                 trace_dir: str) -> Outcome:
    import jax
    from repro import tracing
    from repro.core import (SLO, EngineConfig, GoRouting, Request,
                            RouterConfig, make_policy)
    from repro.core.estimator import BatchLatencyEstimator
    from repro.serving import (AdmissionError, Engine, FrontendConfig,
                               ServiceFrontend)

    cfg, mix = cell.config, cell.traffic
    serve = cfg["serve"]
    slo = SLO(mix["slo"]["ttft_s"], mix["slo"]["tpot_s"])
    sched = dict(serve["scheduler"])
    policy_name = sched.pop("policy")
    token_budget = int(sched.pop("token_budget"))
    est = BatchLatencyEstimator(a_p=1e-8, b_p=1e-8, c_p=1e-4, a_d=1e-8,
                                b_d=1e-3, t_c=1e-2)
    fe = ServiceFrontend(
        GoRouting(est, RouterConfig(pd_mode="coloc")), est,
        FrontendConfig(max_inflight=int(mix["max_inflight"])))
    rec = Recorder(traced=trace)
    devices = jax.devices()[:cell.chips]
    replicas = int(serve["replicas"])
    engines = []
    for i in range(replicas):
        eng = Engine(arch, params,
                     EngineConfig(w_p=float(mix["w_p"]),
                                  w_d=float(mix["w_d"]),
                                  token_budget=token_budget),
                     make_policy(policy_name, **sched),
                     num_blocks=int(serve["num_blocks"]),
                     block_size=int(serve["block_size"]),
                     max_ctx=int(serve["max_ctx"]),
                     packed_prefill=bool(serve["packed_prefill"]),
                     device=devices[i] if cell.chips > 1 else None)
        fe.add_instance(eng)
        instrument(eng, rec)
        engines.append(eng)
    await fe.start()

    def make_request(r: traffic.Req, output_len: int):
        return Request(prompt_len=r.prompt_len, output_len=output_len,
                       arrival=0.0, slo=slo, priority=r.priority,
                       weight=r.weight, client=r.priority)

    consumers = []

    async def send(r: traffic.Req, sent: e2e.Sent, output_len: int):
        with rec.span("generator"):
            sent.sent = time.monotonic()
            try:
                stream = await fe.submit(make_request(r, output_len),
                                         r.prompt)
            except AdmissionError as e:
                sent.failed, sent.error = True, repr(e)
                return
        sent.rid = stream.request.rid
        consumers.append(asyncio.ensure_future(_consume(stream, sent)))

    out = Outcome(recs=[], t0=t0, t_open=0.0, t_close=0.0, rec=rec)
    traced = False
    try:
        # -- warm-up: the cell's own shapes, one burst, short outputs ----
        tw = time.monotonic()
        warm_recs = [e2e.Sent(r, tw) for r in warm]
        for r, s in zip(warm, warm_recs):
            await send(r, s, min(r.output_len, int(mix["warm_output"])))
        await asyncio.gather(*consumers)
        consumers.clear()
        out.warm_s = time.monotonic() - tw
        bad = [s.error for s in warm_recs if s.failed]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
        out.compiles["warm"] = counter.snap()

        # -- ramp and window: the open loop ------------------------------
        ramp = float(mix["ramp_s"])
        t_open = time.monotonic() + ramp
        t_close = t_open + seconds
        out.t_open, out.t_close = t_open, t_close
        recs = [e2e.Sent(r, t_open + r.due) for r in reqs]
        out.recs = recs
        window_ann = None
        i = 0
        opened = False
        while True:
            now = time.monotonic()
            if trace and not traced and now >= t_open - 3.0:
                tracing.enable()           # the program's own spans too
                opts = jax.profiler.ProfileOptions()
                opts.host_tracer_level = 1       # TraceMe spans, no more
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                traced = True
                continue
            if not opened and now >= t_open:
                opened = True
                out.stats = [[_stats(e)] for e in engines]
                out.est = [[e.est.as_dict()] for e in engines]
                out.queue.append(_queue(recs[:i]))
                out.compiles["open"] = counter.snap()
                if trace:
                    window_ann = jax.profiler.TraceAnnotation("cb:window")
                    window_ann.__enter__()
            if i < len(recs) and recs[i].due <= now:
                s = recs[i]
                await send(s.req, s, s.req.output_len)
                out.lateness.append(s.sent - s.due)
                i += 1
                continue
            nxt = min(recs[i].due if i < len(recs) else t_close,
                      t_close if opened else t_open)
            if trace and not traced:
                nxt = min(nxt, t_open - 3.0)
            if now >= t_close and opened:
                break
            await asyncio.sleep(max(0.0, nxt - time.monotonic()))
        out.compiles["close"] = counter.snap()
        out.queue.append(_queue(recs[:i]))
        for e, st, es in zip(engines, out.stats, out.est):
            st.append(_stats(e))
            es.append(e.est.as_dict())
        if window_ann is not None:
            window_ann.__exit__(None, None, None)
        if traced:
            jax.profiler.stop_trace()
            traced = False
        out.memory_peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices)
        # the check samples finished requests: keep serving, with nothing
        # new sent, until they hold the sample's tokens or DRAIN_S pass
        want = int(cfg["check"]["sample_tokens"])
        t_drain = time.monotonic()
        while (sum(r.req.output_len for r in recs if r.finished) < want
               and time.monotonic() - t_drain < DRAIN_S):
            await asyncio.sleep(0.25)
        out.drain_s = time.monotonic() - t_drain
    finally:
        tracing.disable()
        await fe.stop()
        for d in fe.drivers.values():
            d._thread.join(timeout=600.0)
        for e in engines:              # background KV copies end too
            e.flush_transfers()
            if e.worker is not None:
                e.worker.stop()
        for c in consumers:
            c.cancel()
        await asyncio.gather(*consumers, return_exceptions=True)
        out.driver_errors = {k: repr(v) for k, v in fe.driver_errors.items()}
        if trace and traced:
            jax.profiler.stop_trace()
        del engines, fe
    return out


def measure(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
            *, chip: bool = True, trace_dir: str | None = None,
            rate: float | None = None):
    """Set up, ramp and measure one window; returns ``(outcome, params,
    device)`` with the engine state freed and the weights kept for the
    reference."""
    import jax
    cache = use_compile_cache(jax)
    if chip:
        require_chip(jax, cell.chips)
    counter = CompileCounter()
    from .weights import make_params

    cfg, mix = cell.config, cell.traffic
    dev = jax.devices()[0]
    log(f"cell {cell.name}: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} compile_cache={cache}")
    arch = arch_config(cfg)
    if rate is not None:
        mix = cell.traffic = dict(mix, rate_rps=rate)
    reqs = traffic.build(mix, seconds, seed, cfg["vocab_size"])
    warm = traffic.build(mix, seconds, WARM_SEED,
                         cfg["vocab_size"])[:int(mix["warm_requests"])]
    tp = time.monotonic()
    params = make_params(cfg, seed)
    log(f"weights from seed {seed}: {time.monotonic() - tp:.3f} s")
    out = asyncio.run(_serve(cell, params, arch, reqs, warm, seconds,
                             trace, t0, counter, trace_dir or ""))
    gc.collect()
    return out, params, dev


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        *, chip: bool = True, trace_dir: str | None = None,
        rate: float | None = None) -> dict:
    """One run; returns the result object (the last line's content) and
    prints its diagnostics on standard error."""
    tmp = None
    if trace and trace_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        trace_dir = tmp.name
    try:
        out, params, dev = measure(cell, seed, seconds, trace, t0, chip=chip,
                                   trace_dir=trace_dir, rate=rate)
        return _result(cell, out, params, seed, trace, trace_dir, dev)
    finally:
        if tmp is not None:
            tmp.cleanup()


def sample_gaps(cell: Cell, out: Outcome, params, seed: int,
                control: bool = False) -> dict:
    """The correctness readings over the seeded sample of finished
    requests: per request the served tokens' gaps under the reference,
    and with ``control`` also the bfloat16 control's."""
    from . import reference
    cfg = cell.config
    finished = [r for r in out.recs if r.finished]
    sample = judge.pick_sample(finished, seed,
                               int(cfg["check"]["sample_tokens"]),
                               also=[r.rid in out.rec.preempted
                                     for r in finished])
    prog, ctrl = [], []
    for s in sample:
        served = np.asarray(s.tokens[:s.req.output_len])
        extra = ([reference.control_tokens(params, cfg, s.req.prompt, served)]
                 if control else [])
        r = reference.readings(params, cfg, s.req.prompt, served, extra)
        prog.append(r["gap"])
        if control:
            ctrl.append(r["extra_gaps"][0])
    return {"sample": sample, "program": prog, "control": ctrl}


def _result(cell, out: Outcome, params, seed, trace, trace_dir, dev) -> dict:
    import jax
    cfg, mix = cell.config, cell.traffic
    t_open, t_close = out.t_open, out.t_close
    window = e2e.due_in(out.recs, t_open, t_close)
    failed = [r for r in window if r.failed]
    lat = np.asarray(out.lateness or [0.0])
    cw = {k: out.compiles["close"][k] - out.compiles["open"][k]
          for k in out.compiles["close"]}
    log(f"set-up {t_open - out.t0:.3f} s (warm-up burst {out.warm_s:.3f} s, "
        f"ramp {mix['ramp_s']} s); compiles warm={out.compiles['warm']} "
        f"in window={cw}")
    log(f"window {t_close - t_open:.3f} s: {len(window)} requests due, "
        f"{sum(r.finished for r in window)} finished, {len(failed)} failed; "
        f"generator late p50 {np.median(lat) * 1e3:.3f} ms max "
        f"{lat.max() * 1e3:.3f} ms")
    log(f"queue (waiting, decoding): at open {out.queue[0]}, at close "
        f"{out.queue[-1]}; offered {len(window) / (t_close - t_open):.3f} "
        f"req/s, rate {mix['rate_rps']}")
    for i, (a, b) in enumerate(out.stats):
        log(f"replica {i} in window: {_changed(a, b)}")
        log(f"replica {i} estimator at open {out.est[i][0]}, at close "
            f"{out.est[i][1]}")
    plans = [p for p in out.rec.plans if t_open <= p[0] < t_close]
    log(f"plans in window: {sum(1 for p in plans if p[2])} with work, "
        f"{sum(1 for p in plans if not p[2] and p[3])} empty while "
        f"requests were queued; preempted requests {len(out.rec.preempted)}")
    if out.driver_errors:
        log(f"driver errors: {out.driver_errors}")
    ttft, itl = (e2e.ttft_s(out.recs, t_open, t_close),
                 e2e.itl_s(out.recs, t_open, t_close))
    log(f"tails, not judged: TTFT p95 {e2e.p95(ttft or [0]) * 1e3:.3f} ms "
        f"over {len(ttft)} requests; gap p95 "
        f"{e2e.p95(itl or [0]) * 1e3:.3f} ms over {len(itl)} gaps; gain "
        f"{e2e.gain_ratio(out.recs, t_open, t_close, mix['slo']['ttft_s'], mix['slo']['tpot_s'], mix['w_p'], mix['w_d']):.6f}")

    # -- correctness: the reference over a sample of finished requests --
    t_ref = time.monotonic()
    g = sample_gaps(cell, out, params, seed)
    nums = judge.numbers(g["program"])
    ok, check = judge.judge(nums, cfg["check"]["limits"])
    if out.driver_errors:
        ok = False
    log(f"reference check after a {out.drain_s:.3f} s drain: "
        f"{len(g['sample'])} requests "
        f"({sum(r.rid in out.rec.preempted for r in g['sample'])} "
        f"preempted), "
        f"{sum(len(x) for x in g['program'])} served tokens, "
        f"{time.monotonic() - t_ref:.3f} s; readings {nums}")

    metrics, device = {}, {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": out.memory_peak}
    result = {"correct": ok, "attempted": len(window),
              "failed": len(failed), "metrics": metrics, "device": device}
    from .peaks import peaks_for
    from .readers import Context
    from .spec import load_reader
    ctx = Context(out=out, cfg=cfg, mix=mix, chips=cell.chips,
                  peak=peaks_for(dev.device_kind) if dev.platform == "tpu"
                  else None)
    if trace:
        from . import program_trace, trace_reduce
        t_tr = time.monotonic()
        paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        ctx.red = trace_reduce.reduce(trace_reduce.load(str(paths[-1])),
                                      cell.chips)
        device["busy_s"] = ctx.red["busy_s"]
        device["window_s"] = ctx.red["window_s"]
        result["breakdown"] = trace_reduce.breakdown(ctx.red)
        top = sorted(ctx.red["ops"].items(), key=lambda kv: -kv[1][1])
        log(f"trace reduced in {time.monotonic() - t_tr:.3f} s: "
            f"modules {ctx.red['modules']}; top ops {top[:15]}")
        t_tr = time.monotonic()
        ctx.program = program_trace.collect(str(paths[-1]))
        if ctx.program is None:
            log("program spans: none in the window")
        else:
            log(f"program spans read in {time.monotonic() - t_tr:.3f} s: "
                f"{len(ctx.program['spans'])} in the window; idle by "
                f"innermost span "
                f"{program_trace.table(ctx.program['attribution'])}; "
                f"clock {ctx.program['clock']}")
            log(f"programs traced, lowered, compiled or loaded in the "
                f"window: {ctx.program['programs']}")
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["check"] = check
    for k, c in check.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return result
