"""End-to-end metrics from the load generator's own stamps.

Every time is on the client's clock (``time.monotonic()`` where the stream
consumer receives a token) and counts from the request's due instant, not
from when it was sent, so a stall that delays sending shows.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Sent:
    """One request as the load generator saw it."""
    req: object                 # traffic.Req
    due: float                  # absolute due instant (monotonic s)
    sent: float | None = None   # when submit() was called
    rid: int | None = None      # the program's request id
    times: list = field(default_factory=list)    # token receive stamps
    tokens: list = field(default_factory=list)
    failed: bool = False        # refused at admission or failed by a driver
    error: str = ""

    @property
    def finished(self) -> bool:
        return (not self.failed
                and len(self.tokens) >= self.req.output_len)


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))


def due_in(recs, t_open, t_close):
    return [r for r in recs if t_open <= r.due < t_close]


def ttft_s(recs, t_open, t_close) -> list:
    """TTFT of every request due in the window; a request with no first
    token by the close (or refused, or failed) is censored there."""
    out = []
    for r in due_in(recs, t_open, t_close):
        first = r.times[0] if (r.times and not r.failed) else None
        out.append((first if first is not None and first < t_close
                    else t_close) - r.due)
    return out


def itl_s(recs, t_open, t_close) -> list:
    """Every gap between consecutive tokens of one request whose later
    token arrived in the window, and, for each request still decoding at
    the close, its open gap censored there (close minus its last token), so
    a stall shows even where no token ends it."""
    gaps = []
    for r in recs:
        t = r.times
        for a, b in zip(t, t[1:]):
            if t_open <= b < t_close:
                gaps.append(b - a)
        before = [x for x in t if x < t_close]
        if before and len(before) < r.req.output_len:
            gaps.append(t_close - before[-1])
    return gaps


def gain_ratio(recs, t_open, t_close, ttft_slo, tpot_slo, w_p, w_d) -> float:
    """Paper Eq. 3 over the tokens whose fixed deadline
    ``due + TTFT + (i-1) * TPOT`` falls in the window: the weight earned by
    tokens received before their deadline over the weight of all of them."""
    got = ideal = 0.0
    for r in recs:
        n = r.req.output_len
        i0 = max(1, int(np.ceil((t_open - r.due - ttft_slo) / tpot_slo)) + 1)
        for i in range(i0, n + 1):
            dl = r.due + ttft_slo + (i - 1) * tpot_slo
            if dl < t_open:
                continue
            if dl >= t_close:
                break
            w = (w_p if i == 1 else w_d) * r.req.weight
            ideal += w
            if (not r.failed and len(r.times) >= i
                    and r.times[i - 1] < dl):
                got += w
    return got / ideal if ideal > 0 else float("nan")
