"""decode_attn_roofline.sat: Least time for the valid pages' bytes and
operations over the paged decode kernel's device time, in %.
"""
from chipbench.readers import decode_attn_roofline as read

LAYER = 'kernels (kernels/paged_attention.py, kernels/chunked_prefill.py)'
SOURCE = 'device_trace'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
