"""End-to-end metrics on hand-made client stamps."""
from types import SimpleNamespace as NS

import pytest

from chipbench import e2e


def rec(due, times, out_len, weight=1.0, failed=False):
    s = e2e.Sent(NS(output_len=out_len, weight=weight), due)
    s.times = list(times)
    s.tokens = [1] * len(times)
    s.failed = failed
    return s


def test_ttft_counts_censored_and_failed():
    recs = [rec(10.0, [10.5, 10.6], 2), rec(11.0, [], 2),
            rec(12.0, [25.0], 2), rec(13.0, [], 2, failed=True),
            rec(30.0, [30.1], 1)]                     # due after the close
    assert e2e.ttft_s(recs, 10.0, 20.0) == pytest.approx(
        [0.5, 9.0, 8.0, 7.0])


def test_itl_keeps_gaps_ending_in_window():
    recs = [rec(0.0, [9.0, 10.5, 11.0, 21.0], 4),
            rec(0.0, [10.2, 10.4, 10.6], 3)]          # finished: no open gap
    # the first is still decoding at the close: its open gap 20 - 11
    assert e2e.itl_s(recs, 10.0, 20.0) == pytest.approx(
        [1.5, 0.5, 9.0, 0.2, 0.2])


def test_a_stalled_window_raises_itl():
    steady = [9.0 + 0.1 * i for i in range(10)]       # 9.0 .. 9.9
    moving = [rec(0.0, steady + [10.0 + 0.1 * i for i in range(100)], 200)]
    stalled = [rec(0.0, steady + [25.0], 200)]        # nothing in [10, 20)
    assert max(e2e.itl_s(moving, 10.0, 20.0)) < 0.2
    assert e2e.itl_s(stalled, 10.0, 20.0) == pytest.approx([20.0 - 9.9])
    assert e2e.p95(e2e.itl_s(stalled, 10.0, 20.0)) > 10.0


def test_gain_ratio_over_deadlines_in_window():
    # ttft 1 s, tpot 0.5 s: token deadlines 11.0, 11.5, 12.0 for due 10
    r = rec(10.0, [10.9, 11.6, 11.9], 3, weight=2.0)
    g = e2e.gain_ratio([r], 10.0, 20.0, 1.0, 0.5, 4.0, 1.0)
    assert g == pytest.approx((4 * 2 + 1 * 2) / (4 * 2 + 1 * 2 + 1 * 2))
    # a window that starts after the first deadline drops the first token
    g = e2e.gain_ratio([r], 11.2, 20.0, 1.0, 0.5, 4.0, 1.0)
    assert g == pytest.approx(0.5)
    failed = rec(10.0, [], 3, failed=True)
    assert e2e.gain_ratio([failed], 10.0, 20.0, 1.0, 0.5, 4.0, 1.0) == 0.0


def test_p95():
    assert e2e.p95(list(range(1, 101))) == pytest.approx(95.05)


def test_throughput_and_preemption_readers():
    from chipbench.readers import (Context, kv_preempt_per_req,
                                   output_tokens_per_s)
    a = rec(9.0, [9.5, 10.5, 11.0, 21.0], 4)
    a.sent = 9.0
    b = rec(12.0, [13.0, 14.0], 2)
    b.sent = 12.0
    c = rec(15.0, [], 2, failed=True)                 # refused: not admitted
    c.sent = 15.0
    out = NS(recs=[a, b, c], t_open=10.0, t_close=20.0,
             stats=[({"evictions": 3}, {"evictions": 5})])
    ctx = Context(out=out, cfg={}, mix={}, chips=1)
    assert output_tokens_per_s(ctx) == pytest.approx(4 / 10.0)
    assert kv_preempt_per_req(ctx) == pytest.approx(2 / 1)
