"""setup_s: Process start to window open: weights, engine, warm-up burst, ramp."""
from chipbench.readers import setup_s as read

LAYER = 'end to end'
SOURCE = 'host_clock'

__all__ = ["read"]
