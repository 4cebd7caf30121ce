"""The program's tracer (repro.tracing), the spans ``Engine.step`` records,
and the reduction that reads them from a profiler trace
(chipbench/program_trace.py)."""
import glob
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_smoke
from repro.core import SLO, EngineConfig, Request, make_policy
from repro.models import init_params
from repro.serving import Engine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import program_trace as pt  # noqa: E402
from chipbench.spec import load_reader  # noqa: E402

MS = 1_000_000      # ns


@contextmanager
def traced(tmp_path, on=True):
    """Profile the block with the tracer on; afterwards the dict it yields
    holds ``program_trace.load`` of the trace."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    got = {}
    if on:
        tracing.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield got
    finally:
        jax.profiler.stop_trace()
        tracing.disable()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    got.update(pt.load(path[-1]), path=path[-1])


def parents(line):
    """``(span, parent)`` of one thread's nested spans; parent None at the
    root."""
    out, stack = [], []
    for s in sorted(line, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= s[1]:
            stack.pop()
        out.append((s, stack[-1] if stack else None))
        stack.append(s)
    return out


def named(raw, name):
    return [(s, p) for ln in raw["lines"] for s, p in parents(ln)
            if s[0] == name]


# -- the tracer ---------------------------------------------------------------

def test_off_makes_no_annotation(monkeypatch):
    made = []

    class Ann:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    tracing.enable()
    tracing.disable()
    assert tracing.span("a") is tracing.span("b", rid=1)   # one shared no-op
    with tracing.span("engine.step"):
        with tracing.span("kv.reload", rid=3):
            pass
    assert made == []
    tracing.enable()
    try:
        with tracing.span("kv.reload", rid=3):
            pass
    finally:
        tracing.disable()
    assert made == [("ps:kv.reload", {"rid": 3})]


def test_spans_nest_per_thread_in_the_trace(tmp_path):
    opened, release = threading.Event(), threading.Event()

    def other():
        with tracing.span("c"):
            opened.set()
            release.wait(10)

    with traced(tmp_path) as raw:
        with tracing.span("a", rid=5):
            t = threading.Thread(target=other)
            t.start()
            assert opened.wait(10)
            with tracing.span("b"):
                pass
            release.set()
            t.join(10)
    assert not t.is_alive()
    (a, pa), = named(raw, "a")
    (b, pb), = named(raw, "b")
    (c, pc), = named(raw, "c")
    assert pa is None and pb == a and pc is None
    assert a[3] == {"rid": 5}
    line_of = {s[0]: i for i, ln in enumerate(raw["lines"]) for s in ln}
    assert line_of["a"] == line_of["b"] != line_of["c"]
    assert a[1] <= b[1] <= b[2] <= a[2]


def test_span_closes_on_exception(tmp_path):
    with traced(tmp_path) as raw:
        with pytest.raises(KeyError):
            with tracing.span("outer"):
                with tracing.span("inner"):
                    raise KeyError("x")
        with tracing.span("after"):
            pass
    (outer, _), = named(raw, "outer")
    (inner, p_inner), = named(raw, "inner")
    (after, p_after), = named(raw, "after")
    assert p_inner == outer and p_after is None and after[1] >= outer[2]


def test_fresh_jit_yields_compile_spans(tmp_path):
    def fresh_traced_fn(x):
        return x * 3 + 1

    with traced(tmp_path) as raw:
        with tracing.span("exec.launch"):
            jax.jit(fresh_traced_fn)(jnp.arange(5.0)).block_until_ready()
    (launch, _), = named(raw, "exec.launch")
    mine = [(s, p) for s, p in named(raw, tracing.COMPILE)
            if "fresh_traced_fn" in s[3]["fun_name"]]
    assert {s[3]["stage"] for s, _ in mine} == {"trace", "lower", "compile"}
    for s, p in mine:
        assert p == launch and launch[1] <= s[1] <= s[2] <= launch[2]
    rows = pt.programs(dict(raw, window=(launch[1], launch[2])))
    assert {("fresh_traced_fn", "trace", 1),
            ("jit(fresh_traced_fn)", "lower", 1),
            ("jit(fresh_traced_fn)", "compile", 1)} <= {r[:3] for r in rows}


def test_compile_listener_pairs_start_and_end(monkeypatch):
    log = []

    class Ann:
        def __init__(self, name, **kw):
            self.kw = kw

        def __enter__(self):
            log.append(("open", self.kw["fun_name"], self.kw["stage"]))

        def __exit__(self, *exc):
            log.append(("close", self.kw["fun_name"], self.kw["stage"]))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    ev = "/jax/core/compile/backend_compile_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    t = time.time()
    jax.monitoring.record_scalar(ev, t, fun_name="jit(off)")   # tracer off
    tracing.enable()
    try:
        jax.monitoring.record_event_time_span(ev, t, t + 1,
                                              fun_name="jit(off)")
        jax.monitoring.record_scalar(lower, t, fun_name="jit(f)")
        jax.monitoring.record_scalar(ev, t, fun_name="jit(g)")
        jax.monitoring.record_event_time_span(ev, t, t + 1,
                                              fun_name="jit(g)")
        jax.monitoring.record_event_time_span(lower, t, t + 1,
                                              fun_name="jit(f)")
    finally:
        tracing.disable()
    assert log == [("open", "jit(f)", "lower"), ("open", "jit(g)", "compile"),
                   ("close", "jit(g)", "compile"),
                   ("close", "jit(f)", "lower")]


# -- spans of Engine.step -----------------------------------------------------

CFG = get_smoke("qwen1_5_0_5b")
STEP_CHILDREN = {"kv.drain", "sched.form_batch", "kv.sync", "kv.reload",
                 "exec.prefill", "exec.decode", "engine.emit",
                 "engine.finish", "kv.offload", "kv.prefetch", "est.refit"}
EXEC_CHILDREN = {"exec.stage", "exec.launch", "exec.wait"}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, packed):
    return Engine(CFG, params, EngineConfig(eta=1.0, w_p=4.0, tau=1e9),
                  make_policy("slidebatching"), num_blocks=64,
                  block_size=16, max_ctx=128, packed_prefill=packed)


def _submit(eng, rng, n=3):
    for i in range(n):
        r = Request(prompt_len=20 + i, output_len=3, arrival=0.0,
                    slo=SLO(3600.0, 3600.0), priority=1 + i % 2)
        eng.add_request(r, rng.integers(1, CFG.vocab, 20 + i)
                        .astype(np.int32))


def _kids(raw):
    """Each span's children but compile stages, keyed by ``(name, t0,
    t1)``."""
    kids = {}
    for ln in raw["lines"]:
        for s, p in parents(ln):
            kids.setdefault(s[:3], [])
            if p is not None and s[0] != tracing.COMPILE:
                kids.setdefault(p[:3], []).append(s)
    return kids


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "per_req"])
def test_engine_step_has_only_the_documented_children(params, packed,
                                                      tmp_path):
    eng = _engine(params, packed)
    eng.refit_every = 2
    _submit(eng, np.random.default_rng(0))
    with traced(tmp_path) as raw:
        eng.run_until_drained()
    kids = _kids(raw)
    steps = named(raw, "engine.step")
    assert steps and all(p is None for _, p in steps)
    work = [s[:3] for s, _ in steps
            if any(k[0].startswith("exec.") for k in kids[s[:3]])]
    assert len(work) == eng.stats.iterations
    for st in work:
        names = {k[0] for k in kids[st]}
        assert names <= STEP_CHILDREN, names
        assert {"kv.drain", "sched.form_batch"} <= names
        assert sum(k[2] - k[1] for k in kids[st]) <= st[2] - st[1]
        for k in kids[st]:
            if k[0].startswith("exec."):
                sub = {g[0] for g in kids[k[:3]]}
                assert {"exec.stage", "exec.launch"} <= sub, sub
                assert sub <= EXEC_CHILDREN, sub
                assert sum(g[2] - g[1] for g in kids[k[:3]]) <= k[2] - k[1]
    assert named(raw, "est.refit")
    # the no-tracer path serves the same tokens
    eng2 = _engine(params, packed)
    eng2.refit_every = 2
    _submit(eng2, np.random.default_rng(0))
    eng2.run_until_drained()
    assert list(eng2.outputs.values()) == list(eng.outputs.values())


def test_collect_reads_a_traced_engine_run(params, tmp_path):
    eng = _engine(params, False)
    _submit(eng, np.random.default_rng(2))
    with traced(tmp_path) as raw:
        with jax.profiler.TraceAnnotation("cb:window"):
            eng.run_until_drained()
    prog = pt.collect(raw["path"])
    att = prog["attribution"]
    # no device in a CPU trace: the whole window is idle
    assert att["idle_s"] == pytest.approx(att["window_s"])
    assert sum(att["by_span"].values()) == pytest.approx(att["idle_s"])
    assert att["steps_with_work"] == eng.stats.iterations
    ctx = SimpleNamespace(program=prog)
    for name in ("compile_s.sat", "step_host_ms.sat", "idle_in_step_ms.sat"):
        assert load_reader(name).read(ctx) >= 0
    assert 0 < prog["step_host_ms"] <= 1e3 * att["idle_in_step_s"] / (
        att["steps_with_work"])


def test_decode_spans_count_the_pages_read(params, tmp_path):
    """``exec.decode`` carries the pages its rows' attention reads and the
    pages of the bucketed table the launch is given."""
    eng = _engine(params, False)
    _submit(eng, np.random.default_rng(3))
    with traced(tmp_path) as raw:
        eng.run_until_drained()
    spans = [s for s, _ in named(raw, "exec.decode")]
    assert len(spans) == eng.stats.decode_launches > 0
    valid = [int(s[3]["valid_pages"]) for s in spans]
    table = [int(s[3]["table_pages"]) for s in spans]
    assert all(0 < v <= t for v, t in zip(valid, table))
    # every decode row (one token each; prefill emits each request's
    # first) has 21 to 24 tokens of context: two 16-token pages
    assert sum(valid) == 2 * (eng.stats.tokens_out - 3)


def test_a_failed_step_leaves_no_span_open(params, tmp_path):
    eng = _engine(params, True)
    _submit(eng, np.random.default_rng(1), n=1)

    def boom(view):
        raise RuntimeError("planning failed")

    eng.policy.form_batch = boom
    with traced(tmp_path) as raw:
        with pytest.raises(RuntimeError):
            eng.step()
        with tracing.span("after"):
            pass
    (step, _), = named(raw, "engine.step")
    (plan, p_plan), = named(raw, "sched.form_batch")
    (after, p_after), = named(raw, "after")
    assert p_plan == step and p_after is None and after[1] >= step[2]


# -- attribution of a trace (chipbench/program_trace.py) ----------------------

def _raw(lines, ops, window=(0, 100 * MS)):
    return {"devices": [{"name": "/device:TPU:0", "modules": [],
                         "ops": [("op", a, b) for a, b in ops]}],
            "lines": lines, "window": window}


def test_idle_goes_to_the_innermost_span():
    """A 10 ms gap that ``sched.form_batch`` covers 9 ms of: 9 ms to it,
    1 ms to the ``engine.step`` around it."""
    driver = [("engine.step", 0, 20 * MS), ("sched.form_batch", 1 * MS,
                                            10 * MS),
              ("exec.decode", 10 * MS, 20 * MS)]
    att = pt.attribute(_raw([driver], [(10 * MS, 100 * MS)]))
    assert att["by_span"] == pytest.approx({"sched.form_batch": 0.009,
                                            "engine.step": 0.001,
                                            "none": 0.0})
    assert sum(att["by_span"].values()) == pytest.approx(att["idle_s"])
    assert att["idle_in_step_s"] == pytest.approx(0.010)
    assert att["steps"] == att["steps_with_work"] == 1


def test_driver_thread_first_then_others_then_none():
    driver = [("engine.step", 0, 30 * MS), ("exec.decode", 5 * MS, 30 * MS),
              ("exec.wait", 10 * MS, 30 * MS)]
    other = [("generator", 20 * MS, 60 * MS)]
    # device busy 0-5 and 70-100: idle 5-70
    att = pt.attribute(_raw([other, driver], [(0, 5 * MS),
                                              (70 * MS, 100 * MS)]))
    assert att["by_span"] == pytest.approx(
        {"exec.decode": 0.005, "exec.wait": 0.020, "generator": 0.030,
         "none": 0.010})
    assert sum(att["by_span"].values()) == pytest.approx(
        att["window_s"] - att["busy_s"])
    assert att["idle_in_step_s"] == pytest.approx(0.025)


def test_innermost_pieces_and_split():
    pieces = pt.innermost([("p", 0, 10), ("c", 2, 4), ("d", 4, 6)])
    assert pieces == [(0, 2, "p"), (2, 4, "c"), (4, 6, "d"), (6, 10, "p")]
    got, left = pt.split([(1, 3), (9, 12)], pieces)
    assert dict(got) == pytest.approx({"p": 2e-9, "c": 1e-9})
    assert left == [(10, 12)]


def test_clock_check_finds_the_program_a_wait_holds():
    raw = _raw([[("exec.wait", 10 * MS, 20 * MS),
                 ("exec.wait", 40 * MS, 50 * MS),
                 ("exec.wait", 60 * MS, 70 * MS)]], [])
    raw["devices"][0]["modules"] = [
        ("jit_decode_step", 5 * MS, int(19.5 * MS)),
        ("jit_prefill_chunk", 30 * MS, 35 * MS),      # ends before its wait
        ("jit_prefill_chunk", 55 * MS, 68 * MS),
        ("jit_gather", 55 * MS, int(69.9 * MS))]      # not a step program
    c = pt.clock_check(raw)
    assert c["waits"] == 3 and c["held"] == 2
    assert c["lag_ms_median"] == pytest.approx(2.0)
    assert c["lag_ms_max"] == pytest.approx(2.0)


def _steps_raw():
    # one driver line: a step with a decode whose launch compiles, and a
    # step with no work; a compile on another thread
    driver = [("engine.step", 0, 100 * MS, {}),
              ("exec.decode", 5 * MS, 90 * MS, {}),
              ("exec.launch", 10 * MS, 40 * MS, {}),
              ("jit.compile", 15 * MS, 35 * MS, {"fun_name": "jit(f)",
                                                 "stage": "compile"}),
              ("jit.compile", 20 * MS, 30 * MS, {"fun_name": "jit(g)",
                                                 "stage": "lower"}),
              ("exec.wait", 40 * MS, 90 * MS, {}),
              ("engine.step", 100 * MS, 110 * MS, {})]
    other = [("jit.compile", 50 * MS, 60 * MS, {"fun_name": "jit(h)",
                                                "stage": "trace"})]
    return _raw([driver, other], [], window=(0, 200 * MS))


def test_trace_readers():
    raw = _steps_raw()
    assert pt.compile_s(raw) == pytest.approx(0.030)
    assert pt.step_host_ms(raw) == pytest.approx(100 - 20 - 50)
    assert pt.programs(raw) == [
        ("jit(f)", "compile", 1, pytest.approx(0.020)),
        ("jit(h)", "trace", 1, pytest.approx(0.010))]
    # a window that holds no step reads None
    assert pt.compile_s(dict(raw, window=(150 * MS, 200 * MS))) is None
    assert pt.step_host_ms(dict(raw, window=(150 * MS, 200 * MS))) is None


@pytest.mark.parametrize("name", ["compile_s.sat", "step_host_ms.sat",
                                  "idle_in_step_ms.sat"])
def test_new_readers_read_none_without_the_tracer(name, tmp_path):
    mod = load_reader(name)
    ctx = SimpleNamespace(out=SimpleNamespace(t_open=0.0, t_close=1.0),
                          window=(0.0, 1.0), red=None)
    assert mod.read(ctx) is None                         # no field at all
    ctx.program = None
    assert mod.read(ctx) is None                         # no program spans
    # a trace made with the tracer off holds no program span
    with traced(tmp_path, on=False) as raw:
        with jax.profiler.TraceAnnotation("cb:window"):
            with tracing.span("engine.step"):
                jnp.ones(3).block_until_ready()
    assert raw["window"] is not None and raw["lines"] == []
    assert pt.attribute(raw) is None
