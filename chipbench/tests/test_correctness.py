"""The correctness check on a tiny model on the CPU, where the engine's
float32 matmuls are exact: the served path passes, the bfloat16 control in
its place fails, and so does a token altered where the engine emits it."""
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, judge
from chipbench.spec import Cell, load_json, load_traffic

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**31 + 77


def tiny_cell() -> Cell:
    metric = {"name": "setup_s", "unit": "s", "better": "lower",
              "bound": 0.25, "source": "host_clock"}
    return Cell("tiny", 1, load_json(DATA / "tiny.json"),
                load_traffic("tiny-mix", DATA), [metric], [])


@pytest.fixture(scope="module")
def served():
    cell = tiny_cell()
    out, params, _ = harness.measure(cell, SEED, 12.0, False,
                                     time.monotonic(), chip=False)
    return cell, out, params


def test_served_path_is_correct(served):
    cell, out, params = served
    g = harness.sample_gaps(cell, out, params, SEED)
    assert sum(len(x) for x in g["program"]) >= 5
    ok, check = judge.judge(judge.numbers(g["program"]),
                            cell.config["check"]["limits"])
    assert ok, check


def test_control_in_the_programs_place_is_not_correct():
    """The bfloat16 control's first choice at each position of the mix's
    prompts (teacher-forced on the same tokens), judged as a run is."""
    from chipbench import reference, traffic
    from chipbench.weights import make_params
    cell = tiny_cell()
    cfg = cell.config
    params = make_params(cfg, SEED)
    reqs = traffic.build(cell.traffic, 12.0, SEED, cfg["vocab_size"])[:4]
    rng = np.random.default_rng(0)
    gaps = []
    for r in reqs:
        served = rng.integers(1, cfg["vocab_size"], 32).astype(np.int32)
        ctrl = reference.control_tokens(params, cfg, r.prompt, served)
        gaps.append(reference.readings(params, cfg, r.prompt, served,
                                       [ctrl])["extra_gaps"][0])
    ok, check = judge.judge(judge.numbers(gaps), cfg["check"]["limits"])
    assert not ok, check


def test_result_line_of_a_cpu_run(served):
    cell, out, params = served
    res = harness._result(cell, out, params, SEED, False, None,
                          __import__("jax").devices()[0])
    assert res["correct"] is True
    assert list(res)[-1] == "check"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["metrics"]["setup_s"]["value"] > 0


def test_altered_token_is_caught(monkeypatch):
    from repro.serving.engine import Engine
    emit = Engine._emit
    vocab = load_json(DATA / "tiny.json")["vocab_size"]

    def altered(self, r, tok, emitted):
        if len(self.outputs[r.rid]) == 2:          # third token of each
            tok = (tok + 1) % vocab
        return emit(self, r, tok, emitted)

    monkeypatch.setattr(Engine, "_emit", altered)
    cell = tiny_cell()
    res = harness.run(cell, SEED, 8.0, False, time.monotonic(), chip=False)
    assert res["correct"] is False
    assert res["check"]["gap_mean"]["value"] > \
        res["check"]["gap_mean"]["limit"]


def test_sample_holds_the_longest():
    from types import SimpleNamespace as NS
    recs = [NS(req=NS(prompt=np.zeros(n), output_len=o))
            for n, o in [(10, 5), (100, 50), (20, 5), (30, 5)]]
    s = judge.pick_sample(recs, 3, 60)
    assert s[0] is recs[1] and sum(r.req.output_len for r in s) >= 55
    assert judge.pick_sample([], 3, 60) == []
    ok, check = judge.judge({}, {"gap_max": 1.0})
    assert not ok
