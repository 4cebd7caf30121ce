"""What a program adds reaches the readers with no change to the harness:
every ``EngineStats`` field, and every program span in a traced window
with its attributes; and the program's tracer is on in a traced run only.
"""
import dataclasses
import glob
import json
import time
from types import SimpleNamespace

import jax
import pytest

from chipbench import harness, program_trace, readers, spec
from repro import tracing
from repro.serving.engine import Engine, EngineStats
from test_correctness import SEED, tiny_cell

MS = 1_000_000      # ns
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
PROGRAM_METRICS = ("compile_s.sat", "step_host_ms.sat", "idle_in_step_ms.sat")


# -- engine counters ----------------------------------------------------------

@dataclasses.dataclass
class MoreStats(EngineStats):
    experts_read: int = 0          # a counter a later program adds


def test_every_engine_counter_reaches_the_readers():
    eng = SimpleNamespace(stats=MoreStats(evictions=2, experts_read=5,
                                          spec_depth_hist={1: 2}))
    a = harness._stats(eng)
    assert {f.name for f in dataclasses.fields(MoreStats)} == set(a)
    assert a["experts_read"] == 5 and a["evictions"] == 2
    eng.stats.experts_read += 3
    eng.stats.evictions += 4
    eng.stats.spec_depth_hist[1] += 1
    eng.stats.t_block_measured = 0.5
    b = harness._stats(eng)
    assert a["spec_depth_hist"] == {1: 2}        # a snapshot, not the dict
    # the window's log line names what moved, and only that
    assert harness._changed(a, b) == (
        "evictions=4 t_block_measured=0.0->0.5 "
        "spec_depth_hist={1: 2}->{1: 3} experts_read=3")
    # kv_preempt_per_req reads evictions from the snapshots as before
    recs = [harness.e2e.Sent(None, due=t, sent=t) for t in (0.1, 0.5)]
    out = SimpleNamespace(t_open=0.0, t_close=1.0, recs=recs, stats=[[a, b]])
    ctx = readers.Context(out=out, cfg={}, mix={}, chips=1)
    assert readers.kv_preempt_per_req(ctx) == 2.0


# -- program spans --------------------------------------------------------------

def _raw():
    """One driver thread and one other; spans inside, across and outside a
    100 ms window."""
    driver = [("engine.step", 5 * MS, 40 * MS, {}),
              ("exec.decode", 6 * MS, 39 * MS, {"valid_pages": 7,
                                                 "table_pages": 16}),
              ("moe.route", 8 * MS, 12 * MS, {"experts_read": 3}),
              ("engine.step", 95 * MS, 130 * MS, {})]     # midpoint after
    other = [("moe.route", -20 * MS, -10 * MS, {"experts_read": 9}),
             ("moe.route", 50 * MS, 54 * MS, {"experts_read": 5})]
    return {"devices": [{"name": "/device:TPU:0", "modules": [],
                         "ops": [("op", 10 * MS, 60 * MS)]}],
            "lines": [driver, other], "window": (0, 100 * MS)}


READER = '''
LAYER = "experts (toy)"
SOURCE = "program_span"
MOVES = "output_tokens_per_s"


def read(ctx):
    spans = ctx.program["spans"] if ctx.program else []
    reads = [a["experts_read"] for name, _, _, a in spans
             if name == "moe.route"]
    return sum(reads) / len(reads) if reads else None
'''


def test_in_window_spans_reach_a_new_reader(tmp_path):
    prog = program_trace.summarize(_raw())
    assert [(s[0], s[1]) for s in prog["spans"]] == [
        ("engine.step", 5 * MS), ("exec.decode", 6 * MS),
        ("moe.route", 8 * MS), ("moe.route", 50 * MS)]
    assert prog["spans"][1][3] == {"valid_pages": 7, "table_pages": 16}
    assert prog["attribution"]["steps_with_work"] == 1
    # a reader file of its own reads a span by name, with its attributes
    (tmp_path / "experts_per_route.sat.py").write_text(READER)
    mod = spec.load_reader("experts_per_route.sat", base_dir=tmp_path)
    out = SimpleNamespace(t_open=0.0, t_close=0.1)
    assert mod.read(readers.Context(out=out, cfg={}, mix={}, chips=1,
                                    program=prog)) == 4.0
    assert mod.read(readers.Context(out=out, cfg={}, mix={}, chips=1)) is None
    # no window, or no program span: nothing to read
    assert program_trace.summarize(dict(_raw(), window=None)) is None
    assert program_trace.summarize(dict(_raw(), lines=[])) is None


# -- the tracer hook, rehearsed on the CPU --------------------------------------

def traced_cell():
    metrics = [m for m in BENCH["per_layer"]
               if m["name"] in PROGRAM_METRICS + ("kv_preempt_per_req.sat",)]
    cell = tiny_cell()
    cell.per_layer = metrics
    return cell


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of the tiny cell, with the tracer's state seen at
    every engine step."""
    seen = []
    step = Engine.step

    def watched(self, *a, **k):
        seen.append(tracing._on)
        return step(self, *a, **k)

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    cell = traced_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Engine, "step", watched)
        out, params, dev = harness.measure(cell, SEED, 8.0, True,
                                           time.monotonic(), chip=False,
                                           trace_dir=trace_dir)
    return SimpleNamespace(cell=cell, out=out, params=params, dev=dev,
                           seen=seen, trace_dir=trace_dir,
                           on_after=tracing._on)


def test_the_tracer_is_on_in_the_traced_part_only(traced):
    assert traced.seen[0] is False            # warm-up: off
    assert True in traced.seen                # window: on
    assert traced.on_after is False


def test_the_tracer_is_off_after_a_run_that_raises(monkeypatch, tmp_path):
    on = []

    def boom(sent):
        on.append(tracing._on)
        raise RuntimeError("failed at the window's open")

    monkeypatch.setattr(harness, "_queue", boom)
    with pytest.raises(RuntimeError, match="window's open"):
        harness.measure(traced_cell(), SEED, 8.0, True, time.monotonic(),
                        chip=False, trace_dir=str(tmp_path / "t"))
    assert on == [True] and tracing._on is False
    jax.profiler.start_trace(str(tmp_path / "again"))   # none left running
    jax.profiler.stop_trace()


def test_a_traced_run_reads_the_program_spans(traced):
    """A CPU trace has no device plane, so the whole window reads idle; the
    program's spans are there all the same, and the three readers that
    need them read numbers."""
    path = sorted(glob.glob(f"{traced.trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    prog = program_trace.collect(path)
    att = prog["attribution"]
    assert att["busy_s"] == 0 and att["steps_with_work"] > 0
    names = {s[0] for s in prog["spans"]}
    assert {"engine.step", "sched.form_batch", "exec.wait"} <= names
    lo, hi = program_trace.load(path)["window"]
    assert all(lo <= (s[1] + s[2]) / 2 < hi for s in prog["spans"])
    res = harness._result(traced.cell, traced.out, traced.params, SEED, True,
                          traced.trace_dir, traced.dev)
    assert res["correct"] is True
    assert set(PROGRAM_METRICS) <= set(res["metrics"])
    assert all(res["metrics"][m]["value"] >= 0 for m in PROGRAM_METRICS)
    assert list(res)[-1] == "check"
