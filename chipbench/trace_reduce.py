"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports.

    python3 chipbench/trace_reduce.py <trace.xplane.pb>   # prints the summary

From the device planes (``/device:TPU:<n>``) it takes every operation event
(line ``XLA Ops``, named by its HLO instruction without the numeric suffix,
so ``%paged_decode_attention.3 = f32[...] custom-call(...)`` counts as
``paged_decode_attention``) and every program event (line ``XLA Modules``,
``jit_decode_step(<hash>)`` counts as ``jit_decode_step``); from the host
planes, the harness's ``cb:*`` spans (see spans.py).  The measured
window is the ``cb:window`` span.  It returns, clipped to that window:

* ``busy_s``: the union of operation intervals, averaged over the devices;
* ``modules``: per program name (``jit_decode_step``...), count and device
  seconds;
* ``ops``: per operation name, count and device seconds;
* ``gaps``: every idle interval of the first device, labelled with the host
  span that overlaps it most (``none`` when the host was in no span).
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict

SPAN_PREFIX = "cb:"
WINDOW = "window"
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
_OP_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return _OP_SUFFIX.sub("", head)


def module_name(text: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return _MODULE_SUFFIX.sub("", text)


def _events(line, rename=None):
    for e in line.events:
        name = rename(e.name) if rename else e.name
        yield name, float(e.start_ns), float(e.duration_ns)


def load(path: str) -> dict:
    """The parts of a trace the reduction reads, as plain lists (ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = list(_events(line, op_name))
                elif line.name == "XLA Modules":
                    dev["modules"] = list(_events(line, module_name))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, t0, dur in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        spans.append((name[len(SPAN_PREFIX):], t0,
                                      t0 + dur))
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "spans": spans}


def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


class _Labeller:
    """Labels a gap with the host span overlapping it most (the shorter
    one on a tie), searching only spans that can reach the gap."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((s[2] - s[1] for s in self.spans), default=0.0)

    def __call__(self, a: float, b: float) -> str:
        import bisect
        i = bisect.bisect_left(self.starts, a - self.longest)
        j = bisect.bisect_left(self.starts, b)
        best, best_key = "none", (0.0, 0.0)
        for name, s0, s1 in self.spans[i:j]:
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                key = (ov, -(s1 - s0))
                if key > best_key:
                    best, best_key = name, key
        return best


def reduce(raw: dict, chips: int = 1) -> dict:
    """Window-clipped busy time, per-program and per-op device time, and
    labelled idle gaps (seconds).  Raises when the trace has no window
    span; a window in which no device operation ran reads busy 0."""
    wins = [(s0, s1) for n, s0, s1 in raw["spans"] if n == WINDOW]
    if not wins:
        raise ValueError("trace holds no cb:window span")
    lo, hi = wins[0]
    devs = [d for d in raw["devices"] if d["ops"] or d["modules"]][:chips]
    if not devs:                # nothing ran: the whole window is idle
        devs = raw["devices"][:1] or [{"ops": [], "modules": []}]
    label = _Labeller([s for s in raw["spans"] if s[0] != WINDOW])
    busy, gaps = [], []
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(lambda: [0, 0.0])
    for i, d in enumerate(devs):
        evs = d["ops"] or d["modules"]
        u = union((t0, t0 + dur) for _, t0, dur in evs)
        u = list(_clip(u, lo, hi))
        busy.append(sum(b - a for a, b in u) / 1e9)
        for name, t0, dur in d["modules"]:
            for a, b in _clip([(t0, t0 + dur)], lo, hi):
                m = modules[name]
                m[0] += 1
                m[1] += (b - a) / 1e9
        for name, t0, dur in d["ops"]:
            for a, b in _clip([(t0, t0 + dur)], lo, hi):
                o = ops[name]
                o[0] += 1
                o[1] += (b - a) / 1e9
        if i == 0:
            edges = [lo] + [x for ab in u for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((label(a, b), (b - a) / 1e9))
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "modules": {k: tuple(v) for k, v in modules.items()},
            "ops": {k: tuple(v) for k, v in ops.items()},
            "gaps": gaps, "window_ns": (lo, hi)}


def breakdown(red: dict, top: int = 10) -> dict:
    """The traced run's ``breakdown``: the device operations that took most
    time, and idle time summed by the host span it fell in."""
    ops = sorted(((k, v[1]) for k, v in red["ops"].items()),
                 key=lambda x: -x[1])[:top]
    by_label = defaultdict(float)
    for label, secs in red["gaps"]:
        by_label[label] += secs
    gaps = sorted(by_label.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def main(argv) -> int:
    import json
    raw = load(argv[0])
    red = reduce(raw)
    out = {k: red[k] for k in ("window_s", "busy_s", "modules")}
    out["breakdown"] = breakdown(red)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
