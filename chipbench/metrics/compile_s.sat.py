"""compile_s.sat: Seconds of the program's outermost ``jit.compile`` spans
whose midpoint lies in the window: host time spent tracing, lowering,
compiling or loading programs while serving.  Reads ``ctx.program``
(program_trace.collect of the traced run's xplane); None without the
program's spans."""
LAYER = 'model step (serving/model_exec.py)'
SOURCE = 'program_span'
MOVES = 'output_tokens_per_s'


def read(ctx):
    prog = getattr(ctx, "program", None)
    return None if prog is None else prog["compile_s"]
