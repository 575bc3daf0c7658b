"""Time in Python's collector (``hyca.python.gc`` spans) per server step over the traced window (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  Read from the program's own
spans and scopes (``bench/program_trace.py``); silent where the program has none."""
from bench.program_trace import gc_ms_per_step as read  # noqa: F401
