"""The trace reduction, on hand-made events and on a recorded TPU trace."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
LABELS = {"plan", "reload", "prefill", "decode", "emit", "generator", "step",
          "none"}


def raw():
    ms = 1_000_000
    dev = {"name": "/device:TPU:0",
           "modules": [("jit_decode_step", 10 * ms, 20 * ms),
                       ("jit_decode_step", 40 * ms, 20 * ms),
                       ("jit_prefill_packed", 70 * ms, 50 * ms)],
           "ops": [("fusion.1", 10 * ms, 15 * ms),
                   ("paged_decode", 24 * ms, 6 * ms),      # overlaps fusion
                   ("fusion.1", 40 * ms, 20 * ms),
                   ("packed_prefill", 70 * ms, 50 * ms)]}
    spans = [("window", 0, 100 * ms), ("plan", 0, 9 * ms),
             ("step", 0, 62 * ms), ("decode", 30 * ms, 40 * ms),
             ("generator", 61 * ms, 69 * ms)]
    return {"devices": [dev], "spans": spans}


def test_busy_modules_ops_and_gaps():
    red = tr.reduce(raw())
    assert red["window_s"] == pytest.approx(0.1)
    # ops union: [10,30] + [40,60] + [70,100 clipped from 120]
    assert red["busy_s"] == pytest.approx(0.070)
    assert red["modules"]["jit_decode_step"] == pytest.approx((2, 0.040))
    assert red["modules"]["jit_prefill_packed"] == pytest.approx((1, 0.030))
    assert red["ops"]["fusion.1"] == pytest.approx((2, 0.035))
    gaps = red["gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.010, 0.010, 0.010])
    # [0,10]: step overlaps 10 ms, plan 9 ms -> step; [30,40]: decode and
    # step overlap alike, the shorter (decode) wins; [60,70]: generator
    assert [g[0] for g in gaps] == ["step", "decode", "generator"]


def test_breakdown_sorts_and_sums():
    b = tr.breakdown(tr.reduce(raw()))
    assert b["device_ops"][0][0] == "fusion.1"
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"step": 0.01, "decode": 0.01, "generator": 0.01})


def test_missing_window_is_an_error_and_an_idle_device_reads_zero():
    r = raw()
    r["spans"] = [s for s in r["spans"] if s[0] != "window"]
    with pytest.raises(ValueError):
        tr.reduce(r)
    r = raw()
    r["devices"][0]["ops"] = r["devices"][0]["modules"] = []
    red = tr.reduce(r)
    assert red["busy_s"] == 0 and not red["modules"]
    assert sum(g[1] for g in red["gaps"]) == pytest.approx(0.1)


def test_union():
    assert tr.union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


@pytest.mark.skipif(not (DATA / "small.xplane.pb").exists(),
                    reason="recorded trace not present")
def test_recorded_tpu_trace():
    """A run of the 2-layer model on one v5e (record_trace.py)."""
    raw_ = tr.load(str(DATA / "small.xplane.pb"))
    assert raw_["devices"] and raw_["devices"][0]["name"] == "/device:TPU:0"
    red = tr.reduce(raw_)
    assert red["window_s"] == pytest.approx(3.0, abs=0.05)
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(s for _, s in red["gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert {g[0] for g in red["gaps"]} <= LABELS
    for name in ("jit_prefill_packed", "jit_decode_step"):
        assert red["modules"][name][0] > 0
    assert red["ops"]["packed_prefill_attention"][0] > 0
    assert red["ops"]["paged_decode_attention"][0] > 0
    # every engine program ran inside the host span that launched it
    lo, hi = red["window_ns"]
    for prog, span in (("jit_prefill_packed", "prefill"),
                       ("jit_decode_step", "decode")):
        spans = [(a, b) for n, a, b in raw_["spans"] if n == span]
        mods = [(t, t + d) for n, t, d in raw_["devices"][0]["modules"]
                if n == prog and lo <= t < hi]
        assert all(any(a <= m0 and m1 <= b + 5e6 for a, b in spans)
                   for m0, m1 in mods)


def test_names_drop_instruction_text_and_suffixes():
    assert tr.op_name("%packed_prefill_attention.5 = f32[16,16,1,3072,64] "
                      "custom-call(%copy-done.44), custom_call_target=x") \
        == "packed_prefill_attention"
    assert tr.op_name("%while = (s32[]) while(%tuple.72)") == "while"
    assert tr.module_name("jit_prefill_packed(9901633785607156963)") == \
        "jit_prefill_packed"
