"""mfu.sat: Model operations of the window's prefill and decode tokens over
window x bf16 peak, in %.
"""
from chipbench.readers import mfu as read

LAYER = 'model step (serving/model_exec.py)'
SOURCE = 'device_trace'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
