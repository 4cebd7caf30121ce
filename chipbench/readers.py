"""Readers shared by the metric files in ``metrics/``.

Each metric is a file ``metrics/<name>.py`` whose ``read(ctx)`` returns the
metric's value, or None where the run holds nothing to read.  ``ctx`` is a
``Context``: the run's outcome (client stamps, harness spans, and every
field of each engine's ``EngineStats`` at the window's open and close), the
cell's configuration and mix, and, in a traced run, the trace reduction
and the program's own spans (``program``, from program_trace.collect: its
``spans`` hold each program span in the window with its attributes, for a
reader to pick by name).  A share of a roofline or a peak is never made up
as 0: with no device time to divide by, the reader returns None.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

import numpy as np

from . import e2e, flops


@dataclass
class Context:
    out: object            # harness.Outcome
    cfg: dict
    mix: dict
    chips: int
    peak: dict | None = None
    red: dict | None = None    # trace_reduce.reduce(...) of a traced run
    program: dict | None = None    # program_trace.collect(...) likewise

    @property
    def window(self):
        return self.out.t_open, self.out.t_close

    @property
    def seconds(self) -> float:
        return self.out.t_close - self.out.t_open

    def in_window(self, rows):
        """Harness records ``(t0, t1, ...)`` whose midpoint is in the
        window."""
        lo, hi = self.window
        return [r for r in rows if lo <= (r[0] + r[1]) / 2 < hi]


# -- end to end (host clock) ----------------------------------------------

def setup_s(ctx):
    return ctx.out.t_open - ctx.out.t0


def ttft_p95_ms(ctx):
    v = e2e.ttft_s(ctx.out.recs, *ctx.window)
    return 1e3 * e2e.p95(v) if v else None


def itl_p95_ms(ctx):
    v = e2e.itl_s(ctx.out.recs, *ctx.window)
    return 1e3 * e2e.p95(v) if v else None


def gain_ratio(ctx):
    m = ctx.mix
    v = e2e.gain_ratio(ctx.out.recs, *ctx.window, m["slo"]["ttft_s"],
                       m["slo"]["tpot_s"], m["w_p"], m["w_d"])
    return None if np.isnan(v) else v


def output_tokens_per_s(ctx):
    """Every output token received inside the window, over its length."""
    lo, hi = ctx.window
    n = sum(1 for r in ctx.out.recs for t in r.times if lo <= t < hi)
    return n / ctx.seconds if n else None


# -- scheduler (harness spans) --------------------------------------------

def queue_wait_p95_ms(ctx):
    """First plan that holds each request due in the window, minus its due
    instant; a request never planned by the close is censored there."""
    lo, hi = ctx.window
    first = {}
    for t0, t1, rids, _ in ctx.out.rec.plans:
        for rid in rids:
            first.setdefault(rid, t1)
    waits = [min(first.get(r.rid, hi), hi) - r.due
             for r in e2e.due_in(ctx.out.recs, lo, hi)]
    return 1e3 * e2e.p95(waits) if waits else None


def sched_ms_per_step(ctx):
    """Host time inside ``form_batch`` per engine step that had work."""
    plans = ctx.in_window(ctx.out.rec.plans)
    steps = sum(1 for p in plans if p[2])
    if not steps:
        return None
    return 1e3 * sum(p[1] - p[0] for p in plans) / steps


# -- KV manager (engine counters) ------------------------------------------

def kv_preempt_per_req(ctx):
    """Evictions in the window over the requests sent in it."""
    sent = [r for r in e2e.due_in(ctx.out.recs, *ctx.window)
            if r.sent is not None and not r.failed]
    if not sent:
        return None
    ev = sum(b["evictions"] - a["evictions"] for a, b in ctx.out.stats)
    return ev / len(sent)


# -- model step and kernels (device trace) --------------------------------

def _module_time(ctx, *names):
    """(launches, device seconds) summed over the named XLA programs;
    None where the trace holds none of them."""
    if ctx.red is None:
        return None
    found = [ctx.red["modules"][n] for n in names if n in ctx.red["modules"]]
    if not found:
        return None
    return sum(f[0] for f in found), sum(f[1] for f in found)


def decode_step_ms(ctx):
    """Device time of ``jit_decode_step`` programs per launch."""
    m = _module_time(ctx, "jit_decode_step")
    return 1e3 * m[1] / m[0] if m and m[0] else None


def prefill_ms_per_ktok(ctx):
    """Device time of the prefill programs (``jit_prefill_packed`` or, per
    request, ``jit_prefill_chunk``) per 1000 prompt tokens the harness saw
    prefilled in the window."""
    m = _module_time(ctx, "jit_prefill_packed", "jit_prefill_chunk")
    toks = sum(n for _, _, segs in ctx.in_window(ctx.out.rec.prefills)
               for _, n in segs)
    return 1e3 * m[1] / (toks / 1e3) if m and toks else None


def mfu(ctx):
    """Model operations of every prefill and decode token in the window
    over window x bf16 peak x chips."""
    if ctx.peak is None:
        return None
    a = flops.arch(ctx.cfg)
    ops = sum(flops.step_flops(a, lens, [])
              for _, _, lens in ctx.in_window(ctx.out.rec.decodes))
    ops += sum(flops.step_flops(a, [], segs)
               for _, _, segs in ctx.in_window(ctx.out.rec.prefills))
    if not ops:
        return None
    return 100.0 * ops / (ctx.seconds * ctx.peak["bf16_flops"] * ctx.chips)


def kernel_time(ctx, pattern: str):
    """Device seconds of the trace's operations whose name matches
    ``pattern``; None where there are none."""
    if ctx.red is None:
        return None
    rx = re.compile(pattern)
    secs = sum(v[1] for k, v in ctx.red["ops"].items() if rx.search(k))
    return secs or None


DECODE_KERNEL = r"^paged_decode_attention$"
PREFILL_KERNEL = r"^(packed|chunked)_prefill_attention$"


def decode_attn_roofline(ctx):
    kt = kernel_time(ctx, DECODE_KERNEL)
    if kt is None or ctx.peak is None:
        return None
    a = flops.arch(ctx.cfg)
    need = 0.0
    for _, _, lens in ctx.in_window(ctx.out.rec.decodes):
        need += flops.roofline_time(*flops.decode_attn_cost(a, lens),
                                    ctx.peak)[0]
    return 100.0 * need / kt if need else None


def prefill_attn_roofline(ctx):
    kt = kernel_time(ctx, PREFILL_KERNEL)
    if kt is None or ctx.peak is None:
        return None
    a = flops.arch(ctx.cfg)
    need = 0.0
    for _, _, segs in ctx.in_window(ctx.out.rec.prefills):
        need += flops.roofline_time(*flops.prefill_attn_cost(a, segs),
                                    ctx.peak)[0]
    return 100.0 * need / kt if need else None


def device_idle_share(ctx):
    if ctx.red is None or ctx.red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.red["busy_s"] / ctx.red["window_s"])
