"""decode_step_ms.tail: Device ms of jit_decode_step per launch."""
from chipbench.readers import decode_step_ms as read

LAYER = 'model step (serving/model_exec.py)'
SOURCE = 'device_trace'
MOVES = 'itl_p95_ms'

__all__ = ["read"]
