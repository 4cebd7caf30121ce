"""sched_ms_per_step.sat: Host ms inside form_batch per engine step with work."""
from chipbench.readers import sched_ms_per_step as read

LAYER = 'scheduler (core/slidebatching.py)'
SOURCE = 'program_span'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
