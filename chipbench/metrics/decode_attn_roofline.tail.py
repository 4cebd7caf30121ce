"""decode_attn_roofline.tail: Least time for the valid pages' bytes and
operations over the paged decode kernel's device time, in %.
"""
from chipbench.readers import decode_attn_roofline as read

LAYER = 'kernels (kernels/paged_attention.py, kernels/chunked_prefill.py)'
SOURCE = 'device_trace'
MOVES = 'itl_p95_ms'

__all__ = ["read"]
