"""device_idle_share.tail: 100 x (1 - union of device-busy intervals / traced
window).
"""
from chipbench.readers import device_idle_share as read

LAYER = 'device (TPU v5e)'
SOURCE = 'device_trace'
MOVES = 'itl_p95_ms'

__all__ = ["read"]
