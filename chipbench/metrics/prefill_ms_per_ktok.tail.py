"""prefill_ms_per_ktok.tail: Device ms of jit_prefill_packed per 1000 prompt
tokens packed.
"""
from chipbench.readers import prefill_ms_per_ktok as read

LAYER = 'model step (serving/model_exec.py)'
SOURCE = 'device_trace'
MOVES = 'ttft_p95_ms'

__all__ = ["read"]
