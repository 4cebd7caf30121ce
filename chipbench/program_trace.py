"""Read a traced run's device-idle time, compile time and step host path
from the program's own spans.

    python3 chipbench/program_trace.py <trace.xplane.pb>   # prints them

With its tracer on (src/repro/tracing.py) the program enters each of its
spans, and each of JAX's compile stages (``jit.compile``), as a profiler
annotation ``ps:<name>`` on the thread that runs it, so the trace holds
them on the device events' own clock.  From one ``.xplane.pb`` this module
reads the first device's busy intervals (as trace_reduce.py does), the
harness's ``cb:window`` span and the program's spans.  It splits every
idle interval of the window by the innermost span open over each part of
it: on the engine's driver thread first (a thread whose line holds
``ps:engine.step``), then on the other threads, and ``none`` for what no
span covers.  On the same spans it reads the seconds of compile stages in
the window and the host path of each step, and it hands on every program
span in the window with its attributes, so that a reader of a span that a
later program adds needs no change here.  Where the trace holds no
program span (the program's tracer was off or absent) there is nothing
to read, and ``collect`` returns None.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.trace_reduce import (  # noqa: E402
    _clip, module_name, op_name, union)

PREFIX = "ps:"
WINDOW = "cb:window"
STEP = "engine.step"
COMPILE = "jit.compile"
EXEC = ("exec.prefill", "exec.decode")
# the step programs whose results an ``exec.wait`` fetches
STEP_PROGRAM = re.compile(r"^jit_(decode_step|verify_step|prefill_\w+)$")


def load(path: str) -> dict:
    """The parts of a trace this module reads, as plain lists (ns): the
    first device's operations and programs, the window, and per host
    thread the program's spans ``(name, t0, t1, attrs)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, lines, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [(op_name(e.name), e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [(module_name(e.name), e.start_ns,
                                       e.start_ns + e.duration_ns)
                                      for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    t0 = float(e.start_ns)
                    t1 = t0 + float(e.duration_ns)
                    if e.name == WINDOW and window is None:
                        window = (t0, t1)
                    elif e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], t0, t1,
                                      {k: v for k, v in e.stats}))
                if spans:
                    lines.append(spans)
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "lines": lines, "window": window}


def innermost(spans) -> list:
    """``(a, b, name)`` pieces of one thread's properly nested spans, each
    named for the innermost span open over it; sorted and disjoint."""
    pieces, stack, cur = [], [], float("-inf")

    def advance(to):
        nonlocal cur
        while stack and stack[-1][1] <= to:
            name, end = stack.pop()
            if end > cur:
                pieces.append((cur, end, name))
                cur = end
        if stack and to > cur:
            pieces.append((cur, to, stack[-1][0]))
        cur = max(cur, to)

    for s in sorted(spans, key=lambda s: (s[1], -s[2])):
        advance(s[1])
        stack.append((s[0], s[2]))
    advance(float("inf"))
    return pieces


def split(idle, pieces):
    """Seconds of the sorted disjoint intervals ``idle`` under each piece's
    name, and the parts no piece covers."""
    got, left, j = defaultdict(float), [], 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        cur, k = a, j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(pieces[k][0], cur), min(pieces[k][1], b)
            if lo > cur:
                left.append((cur, lo))
            if hi > lo:
                got[pieces[k][2]] += (hi - lo) / 1e9
                cur = hi
            k += 1
        if cur < b:
            left.append((cur, b))
    return got, left


def _in_window(raw, spans, name=None):
    """The spans (of ``name``, or all) whose midpoint is in the window."""
    lo, hi = raw["window"]
    return [s for s in spans if (name is None or s[0] == name)
            and lo <= (s[1] + s[2]) / 2 < hi]


def _drivers(raw) -> list:
    """The lines that hold ``engine.step`` spans, most steps first."""
    return sorted((ln for ln in raw["lines"] if any(s[0] == STEP for s in ln)),
                  key=lambda ln: -sum(s[0] == STEP for s in ln))


def _steps(raw) -> list:
    """``(line, [(t0, t1, with_work)])`` of each driver line's in-window
    ``engine.step`` spans; a step has work where an ``exec.*`` span opens
    inside it."""
    out = []
    for ln in _drivers(raw):
        starts = sorted(s[1] for s in ln if s[0] in EXEC)
        steps = []
        for _, a, b, *_ in _in_window(raw, ln, STEP):
            i = bisect.bisect_left(starts, a)
            steps.append((a, b, i < len(starts) and starts[i] < b))
        out.append((ln, steps))
    return out


def attribute(raw: dict) -> dict | None:
    """Idle seconds of the window by innermost span, idle seconds inside
    ``engine.step`` spans, and the steps with work; None where the trace
    has no window or no program span."""
    if raw["window"] is None or not raw["lines"]:
        return None
    lo, hi = raw["window"]
    dev = next((d for d in raw["devices"] if d["ops"] or d["modules"]),
               {"ops": [], "modules": []})
    busy = list(_clip(union((a, b) for _, a, b in dev["ops"]
                            or dev["modules"]), lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    drivers = _drivers(raw)
    mine = {id(ln) for ln in drivers}
    others = sorted((ln for ln in raw["lines"] if id(ln) not in mine),
                    key=lambda ln: -len(ln))
    by_span, left = defaultdict(float), idle
    for ln in drivers + others:
        got, left = split(left, innermost(ln))
        for k, v in got.items():
            by_span[k] += v
    by_span["none"] += sum(b - a for a, b in left) / 1e9
    steps = [s for _, st in _steps(raw) for s in st]
    in_step = split(idle, [(a, b, STEP) for a, b in union(
        (s[0], s[1]) for s in steps)])[0].get(STEP, 0.0)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "idle_s": sum(b - a for a, b in idle) / 1e9,
            "by_span": dict(by_span), "idle_in_step_s": in_step,
            "steps": len(steps),
            "steps_with_work": sum(s[2] for s in steps)}


def clock_check(raw: dict) -> dict | None:
    """For each in-window ``exec.wait`` span, the last step program
    (``jit_decode_step``, ``jit_prefill_*``...) to end by the span's end.
    Host spans and device events share one clock where that end lies
    inside the wait (``held``) in every case, a little before the wait's
    end: the lag is the result's fetch and the host's wake-up."""
    if raw["window"] is None or not raw["devices"]:
        return None
    ends = sorted(b for d in raw["devices"][:1]
                  for name, _, b in d["modules"] if STEP_PROGRAM.match(name))
    waits, lags = 0, []
    for ln in raw["lines"]:
        for _, a, b, *_ in _in_window(raw, ln, "exec.wait"):
            waits += 1
            i = bisect.bisect_right(ends, b) - 1
            if i >= 0 and ends[i] >= a:
                lags.append((b - ends[i]) / 1e6)
    lags.sort()
    return {"waits": waits, "held": len(lags),
            "lag_ms_median": lags[len(lags) // 2] if lags else None,
            "lag_ms_max": lags[-1] if lags else None}


def _outermost(spans) -> list:
    """The spans of one line not nested in another of ``spans``."""
    out, end = [], float("-inf")
    for s in sorted(spans, key=lambda s: (s[1], -s[2])):
        if s[1] >= end:
            out.append(s)
            end = s[2]
    return out


def _compiles(raw) -> list:
    """The outermost ``jit.compile`` spans of each line, in the window."""
    return [s for ln in raw["lines"] for s in
            _outermost(_in_window(raw, ln, COMPILE))]


def compile_s(raw: dict) -> float | None:
    """Seconds of outermost ``jit.compile`` spans whose midpoint lies in
    the window: tracing, lowering, compiling or loading programs; None
    where the window holds no ``engine.step``."""
    if raw["window"] is None or not any(st for _, st in _steps(raw)):
        return None
    return sum(s[2] - s[1] for s in _compiles(raw)) / 1e9


def step_host_ms(raw: dict) -> float | None:
    """Mean ms of each in-window ``engine.step`` with an ``exec.*`` span,
    less the union of the ``exec.wait`` and ``jit.compile`` spans inside
    it on its line."""
    if raw["window"] is None:
        return None
    host = []
    for ln, steps in _steps(raw):
        off = union((s[1], s[2]) for s in ln
                    if s[0] in ("exec.wait", COMPILE))
        for a, b, work in steps:
            if work:
                host.append(b - a - sum(y - x for x, y in _clip(off, a, b)))
    return sum(host) / len(host) / 1e6 if host else None


def programs(raw: dict) -> list:
    """``(fun_name, stage, count, seconds)`` of the outermost
    ``jit.compile`` spans in the window, most seconds first."""
    rows = defaultdict(lambda: [0, 0.0])
    for _, a, b, attrs in _compiles(raw):
        r = rows[(attrs.get("fun_name", "?"), attrs.get("stage", "?"))]
        r[0] += 1
        r[1] += (b - a) / 1e9
    return sorted(((f, st, n, t) for (f, st), (n, t) in rows.items()),
                  key=lambda r: -r[3])


def summarize(raw: dict) -> dict | None:
    """What the readers need from ``load``'s lists: the attribution, the
    clock check, ``compile_s``, ``step_host_ms``, ``programs``, and
    ``spans``, every program span of every thread whose midpoint is in the
    window, ``(name, t0, t1, attrs)`` in ns on the trace's clock, in order
    of start; None where the trace holds no window or no program span."""
    att = attribute(raw)
    if att is None:
        return None
    return {"attribution": att, "clock": clock_check(raw),
            "compile_s": compile_s(raw), "step_host_ms": step_host_ms(raw),
            "programs": programs(raw),
            "spans": sorted((s for ln in raw["lines"]
                             for s in _in_window(raw, ln)),
                            key=lambda s: (s[1], -s[2]))}


def collect(xplane: str) -> dict | None:
    """``summarize`` of a traced run's xplane."""
    return summarize(load(xplane))


def table(att: dict, top: int = 10) -> list:
    """The attribution's rows, most seconds first: ``(name, seconds)``."""
    return sorted(att["by_span"].items(), key=lambda kv: -kv[1])[:top]


def main(argv) -> int:
    import json
    prog = collect(argv[0])
    if prog is None:
        print("trace holds no cb:window span or no program span",
              file=sys.stderr)
        return 1
    print(json.dumps(dict(prog, spans=len(prog["spans"]),
                          table=table(prog["attribution"])), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
