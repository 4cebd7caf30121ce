"""kv_preempt_per_req.sat: Evictions from the device pool in the window
(EngineStats.evictions) over the requests sent in it.
"""
from chipbench.readers import kv_preempt_per_req as read

LAYER = 'KV manager (core/blocks.py, serving/kv_pool.py)'
SOURCE = 'program_counter'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
