import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic
from chipbench.spec import ROOT, load_traffic

DATA = Path(__file__).resolve().parent / "data"
MIXES = ["industrial3p-over", "industrial3p-flood", "gamma-mix"]
BIG = 2**31 + 987654321
BEST_SERVED = 231.75      # output tokens/s: the most this model has served


def _mix(name):
    return load_traffic(name, DATA) if (DATA / f"{name}.json").exists() \
        else load_traffic(name)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = _mix(mix)
    a = traffic.build(m, 45, BIG, 1000)
    b = traffic.build(m, 45, BIG, 1000)
    assert [(r.due, r.prompt_len, r.output_len, r.priority) for r in a] == \
        [(r.due, r.prompt_len, r.output_len, r.priority) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_arrivals_and_sizes_in_another_order(mix):
    """Seeds share every arrival with its size; only the tokens differ."""
    m = _mix(mix)
    a = traffic.build(m, 45, 1, 1000)
    b = traffic.build(m, 45, 2, 1000)
    work = lambda rs: [(r.due, r.priority, r.prompt_len, r.output_len)
                       for r in rs]
    assert work(a) == work(b)
    assert len({r.prompt_len for r in a}) > 1
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_arrivals_and_rate(mix):
    m = _mix(mix)
    reqs = traffic.build(m, 200, 5, 1000, rate=2.0)
    lo = -m["ramp_s"]
    assert all(lo <= r.due < 200 for r in reqs)
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)
    n = len(reqs) / (200 + m["ramp_s"])
    assert 1.5 < n < 2.5
    for r in reqs:
        assert len(r.prompt) == r.prompt_len and r.output_len >= 1
        assert r.prompt.dtype == np.int32 and r.prompt.min() >= 1
    hi_p = max(k["prompt"]["hi"] for k in m["kinds"])
    assert max(r.prompt_len for r in reqs) <= hi_p
    prios = {c["priority"] for c in m["classes"]}
    assert {r.priority for r in reqs} == prios


def test_sizes_follow_the_lognormal_mean():
    m = load_traffic("industrial3p-over")
    reqs = traffic.build(m, 600, 3, 1000, rate=2.0)
    mean_in = np.mean([r.prompt_len for r in reqs])
    mean_out = np.mean([r.output_len for r in reqs])
    assert 500 < mean_in < 650 and 180 < mean_out < 240


def test_window_length_only_cuts_the_arrival_process():
    m = load_traffic("industrial3p-over")
    short = traffic.build(m, 20, 3, 1000)
    long = traffic.build(m, 51, 3, 1000)
    assert [(r.due, r.priority) for r in short] == \
        [(r.due, r.priority) for r in long if r.due < 20]


def test_sinusoid_holds_its_rate_over_whole_periods():
    m = load_traffic("industrial3p-over")
    span = 100 * m["arrivals"]["period_s"]
    reqs = traffic.build(dict(m, ramp_s=0), span, 3, 1000)
    assert len(reqs) / span == pytest.approx(m["rate_rps"], rel=0.05)


def test_ramp_precedes_the_window():
    m = load_traffic("industrial3p-over")
    reqs = traffic.build(m, 45, 3, 1000, rate=1.0)
    assert any(r.due < 0 for r in reqs) and any(r.due >= 0 for r in reqs)


def test_flood_is_the_paper_mix_offered_harder():
    over = load_traffic("industrial3p-over")
    flood = load_traffic("industrial3p-flood")
    rate_keys = {"rate_rps", "rate_share_of_knee", "knee_rps", "notes"}
    assert {k for k in over.keys() | flood.keys()
            if over.get(k) != flood.get(k)} == rate_keys | {"name"}
    assert flood["rate_rps"] == pytest.approx(
        flood["knee_rps"] * flood["rate_share_of_knee"])


def test_flood_offers_several_times_the_best_served_rate():
    """Output tokens offered over ramp and window, whatever the seed."""
    m = load_traffic("industrial3p-flood")
    window = json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    reqs = traffic.build(m, window, BIG, 1000)
    offered = sum(r.output_len for r in reqs) / (m["ramp_s"] + window)
    assert offered >= 3 * BEST_SERVED
