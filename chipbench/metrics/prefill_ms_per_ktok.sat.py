"""prefill_ms_per_ktok.sat: Device ms of jit_prefill_packed per 1000 prompt
tokens packed.
"""
from chipbench.readers import prefill_ms_per_ktok as read

LAYER = 'model step (serving/model_exec.py)'
SOURCE = 'device_trace'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
