"""output_tokens_per_s: Every output token received inside the window, over
the window's length.
"""
from chipbench.readers import output_tokens_per_s as read

LAYER = 'end to end'
SOURCE = 'host_clock'

__all__ = ["read"]
