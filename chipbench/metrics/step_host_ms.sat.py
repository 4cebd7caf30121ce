"""step_host_ms.sat: Mean ms of each in-window ``engine.step`` span with an
``exec.*`` child, less its ``exec.wait`` and ``jit.compile`` spans: the
host path of a step that the device can wait on.  Reads ``ctx.program``
(program_trace.collect of the traced run's xplane); None without the
program's spans."""
LAYER = 'engine step (serving/engine.py)'
SOURCE = 'program_span'
MOVES = 'output_tokens_per_s'


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog["step_host_ms"]
