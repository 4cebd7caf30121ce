"""device_idle_share.sat: 100 x (1 - union of device-busy intervals / traced
window).
"""
from chipbench.readers import device_idle_share as read

LAYER = 'device (TPU v5e)'
SOURCE = 'device_trace'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
