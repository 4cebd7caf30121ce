"""queue_wait_p95_ms: p95 of first plan entry minus due instant, requests due
in the window.
"""
from chipbench.readers import queue_wait_p95_ms as read

LAYER = 'scheduler (core/slidebatching.py)'
SOURCE = 'program_span'
MOVES = 'ttft_p95_ms'

__all__ = ["read"]
