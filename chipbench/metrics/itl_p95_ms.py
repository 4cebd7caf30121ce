"""itl_p95_ms: p95 of every gap between consecutive tokens whose later token
arrived in the window.
"""
from chipbench.readers import itl_p95_ms as read

LAYER = 'end to end'
SOURCE = 'host_clock'

__all__ = ["read"]
