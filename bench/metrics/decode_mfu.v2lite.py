"""Model FLOPs of the traced decode steps per second of the traced window, as a share of the chip's bf16 peak (%).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``."""
from bench.layer_metrics import decode_mfu as read  # noqa: F401
