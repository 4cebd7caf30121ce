"""ttft_p95_ms: p95 time to first token of every request due in the window,
from its due instant; censored at the close.
"""
from chipbench.readers import ttft_p95_ms as read

LAYER = 'end to end'
SOURCE = 'host_clock'

__all__ = ["read"]
