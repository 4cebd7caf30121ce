"""Spans of the serving program in the profiler trace, off by default.

    from repro import tracing
    tracing.enable()
    with tracing.span("kv.reload", rid=7):
        ...
    tracing.disable()

Off, ``span()`` returns one shared no-op context after a single flag check:
no profiler annotation is made.

On, each span enters a ``jax.profiler.TraceAnnotation`` named ``ps:<name>``
with its keyword arguments as the event's stats.  A profiler trace then
holds it on the line of the thread that ran it, on the device events' own
clock; spans nest by time on that line.  Outside a profiler session an
annotation records nothing.

While on, JAX's compile stages are annotated too, as ``ps:jit.compile``
with JAX's ``fun_name`` and the ``stage``: ``trace`` (Python to jaxpr),
``lower`` (jaxpr to MLIR) or ``compile`` (backend compile, or its load
from the persistent compilation cache).  Each opens and closes on the
thread that compiles, inside the span that was open there.
"""
from __future__ import annotations

import threading

PREFIX = "ps:"
COMPILE = "jit.compile"

# JAX's compile events (jax/_src/dispatch.py) and the stage each marks
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()
_on = False
_local = threading.local()


def _annotation(name: str, attrs: dict):
    import jax
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)


def span(name: str, **attrs):
    """A context that annotates the profiler trace while the tracer is on."""
    if not _on:
        return _OFF
    return _annotation(name, attrs)


# -- compile stages (JAX monitoring listeners) -------------------------------

def _open_compiles() -> list:
    opened = getattr(_local, "compiles", None)
    if opened is None:
        opened = _local.compiles = []
    return opened


def _compile_open(event: str, value, **kw) -> None:
    """JAX records each compile stage's start as a scalar of the same
    event name; open a ``jit.compile`` annotation for it on this thread."""
    stage = _STAGES.get(event)
    if stage is None or not _on:
        return
    fun = str(kw.get("fun_name", "?"))
    ann = _annotation(COMPILE, {"fun_name": fun, "stage": stage})
    ann.__enter__()
    _open_compiles().append((event, fun, ann))


def _compile_close(event: str, start: float, end: float, **kw) -> None:
    """JAX's end of a compile stage: close the annotation its start
    opened on this thread, if the tracer was on then."""
    if event not in _STAGES:
        return
    fun = str(kw.get("fun_name", "?"))
    opened = _open_compiles()
    for i in range(len(opened) - 1, -1, -1):
        if opened[i][:2] == (event, fun):
            opened.pop(i)[2].__exit__(None, None, None)
            return


# -- the API -----------------------------------------------------------------

def enable() -> None:
    """Annotate the program's spans and compile stages from now on."""
    global _on
    if _on:
        return
    import jax.monitoring as mon
    mon.register_scalar_listener(_compile_open)
    mon.register_event_time_span_listener(_compile_close)
    _on = True


def disable() -> None:
    """Stop annotating; spans already open close as they exit."""
    global _on
    if not _on:
        return
    import jax.monitoring as mon
    _on = False
    mon.unregister_scalar_listener(_compile_open)
    mon.unregister_event_time_span_listener(_compile_close)
