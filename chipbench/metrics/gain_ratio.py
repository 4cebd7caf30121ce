"""gain_ratio: Paper Eq. 3 TDG ratio over the tokens whose deadline falls in
the window.
"""
from chipbench.readers import gain_ratio as read

LAYER = 'end to end'
SOURCE = 'host_clock'

__all__ = ["read"]
