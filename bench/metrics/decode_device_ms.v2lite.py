"""Device time of one execution of the jitted decode step (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``."""
from bench.layer_metrics import decode_device_ms as read  # noqa: F401
