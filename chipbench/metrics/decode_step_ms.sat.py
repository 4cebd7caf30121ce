"""decode_step_ms.sat: Device ms of jit_decode_step per launch."""
from chipbench.readers import decode_step_ms as read

LAYER = 'model step (serving/model_exec.py)'
SOURCE = 'device_trace'
MOVES = 'output_tokens_per_s'

__all__ = ["read"]
