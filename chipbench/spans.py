"""Host spans and counters the harness records around its calls into each
layer of the serving stack.

The program has no spans of its own, so the harness wraps, on each engine
instance it builds: the scheduler's ``form_batch`` (span ``plan``), the
engine's decode and prefill launches, packed or per request (``decode``,
``prefill``), the
pool's reloads (``reload``), the whole iteration (``step``) and the token
hook (``emit``); the load generator adds ``generator``.  Each span is kept
in memory on ``time.monotonic()``; in a traced run it is also a profiler
``TraceAnnotation`` named ``cb:<span>``, so the trace reduction can put it
on the device's clock.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

SPAN_PREFIX = "cb:"


@dataclass
class Recorder:
    traced: bool = False
    spans: list = field(default_factory=list)      # (name, t0, t1)
    plans: list = field(default_factory=list)      # (t0, t1, rids, queued)
    preempted: set = field(default_factory=set)    # rids the scheduler evicted
    decodes: list = field(default_factory=list)    # (t0, t1, lens)
    prefills: list = field(default_factory=list)   # (t0, t1, [(ctx, n)])

    @contextlib.contextmanager
    def span(self, name: str):
        box = [time.monotonic(), None]
        if self.traced:
            import jax
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                try:
                    yield box
                finally:
                    box[1] = time.monotonic()
        else:
            try:
                yield box
            finally:
                box[1] = time.monotonic()
        self.spans.append((name, box[0], box[1]))


def instrument(engine, rec: Recorder) -> None:
    """Wrap one engine's layer entry points with spans (call after the
    frontend has registered the engine, so its token hook is set)."""
    policy = engine.policy
    form_batch = policy.form_batch

    def plan(view):
        with rec.span("plan") as s:
            p = form_batch(view)
        rec.plans.append((s[0], s[1], [e.req.rid for e in p.entries],
                          len(view.queue)))
        rec.preempted.update(r.rid for r in p.evictions)
        return p

    policy.form_batch = plan

    run_decode = engine._run_decode

    def decode(entries, emitted):
        lens = [e.l_kv for e in entries]
        with rec.span("decode") as s:
            run_decode(entries, emitted)
        rec.decodes.append((s[0], s[1], lens))

    engine._run_decode = decode

    for name in ("_run_prefill_packed", "_run_prefill_fallback"):
        run_prefill = getattr(engine, name)

        def prefill(entries, emitted, _run=run_prefill):
            segs = [(e.l_kv, e.n_tokens) for e in entries]
            with rec.span("prefill") as s:
                _run(entries, emitted)
            rec.prefills.append((s[0], s[1], segs))

        setattr(engine, name, prefill)

    for name in ("reload_blocks", "reload_from_device"):
        fn = getattr(engine.pool, name)

        def reload(*a, _fn=fn, **k):
            with rec.span("reload"):
                return _fn(*a, **k)

        setattr(engine.pool, name, reload)

    step = engine.step

    def stepped():
        with rec.span("step"):
            return step()

    engine.step = stepped

    on_token = engine.on_token

    def emit(*a, **k):
        with rec.span("emit"):
            return on_token(*a, **k)

    engine.on_token = emit
