"""Share of the traced window in which no operation ran on the chip (%).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``."""
from bench.layer_metrics import idle_share as read  # noqa: F401
