"""Host time per server step outside device work: wall time of ``server.step()`` minus the part of it in which an operation ran on the chip (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``."""
from bench.layer_metrics import host_ms_per_step as read  # noqa: F401
