"""prefill_attn_roofline.tail: Least time for each packed prefill's causal
operations and bytes over the packed prefill kernel's device time, in %.
"""
from chipbench.readers import prefill_attn_roofline as read

LAYER = 'kernels (kernels/paged_attention.py, kernels/chunked_prefill.py)'
SOURCE = 'device_trace'
MOVES = 'ttft_p95_ms'

__all__ = ["read"]
