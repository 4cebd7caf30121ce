"""sched_ms_per_step.tail: Host ms inside form_batch per engine step with work."""
from chipbench.readers import sched_ms_per_step as read

LAYER = 'scheduler (core/slidebatching.py)'
SOURCE = 'program_span'
MOVES = 'itl_p95_ms'

__all__ = ["read"]
