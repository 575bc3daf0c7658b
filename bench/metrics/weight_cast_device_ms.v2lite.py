"""Device time per decode step of the operations under the ``weights.cast`` scope: the per-step cast of the stored weights (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  Read from the program's own
spans and scopes (``bench/program_trace.py``); silent where the program has none."""
from bench.program_trace import weight_cast_device_ms as read  # noqa: F401
