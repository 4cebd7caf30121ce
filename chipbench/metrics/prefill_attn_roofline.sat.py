"""prefill_attn_roofline.sat: Least time for each packed prefill's causal
operations and bytes over the packed prefill kernel's device time, in %.
"""
from chipbench.readers import prefill_attn_roofline as read

LAYER = 'kernels (kernels/paged_attention.py, kernels/chunked_prefill.py)'
SOURCE = 'device_trace'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
