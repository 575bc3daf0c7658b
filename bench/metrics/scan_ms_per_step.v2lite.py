"""Wall time of ``FaultManager.scan_step`` per server step (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``."""
from bench.layer_metrics import scan_ms_per_step as read  # noqa: F401
