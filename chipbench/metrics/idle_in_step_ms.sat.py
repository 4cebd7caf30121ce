"""idle_in_step_ms.sat: Device-idle ms inside ``engine.step`` spans, on the
trace's own clock, per step with work: idle that the step's own host path
causes, against idle between steps.  Reads ``ctx.program``
(program_trace.collect of the traced run's xplane); None without the
program's spans."""
LAYER = 'engine step (serving/engine.py)'
SOURCE = 'device_trace'
MOVES = 'output_tokens_per_s'


def read(ctx):
    prog = getattr(ctx, "program", None)
    if prog is None or not prog["attribution"]["steps_with_work"]:
        return None
    att = prog["attribution"]
    return 1e3 * att["idle_in_step_s"] / att["steps_with_work"]
