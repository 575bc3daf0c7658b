"""Idle chip time inside the program's ``hyca.server.step`` span under none of its child spans, per traced step (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  Read from the program's own
spans and scopes (``bench/program_trace.py``); silent where the program has none."""
from bench.program_trace import unattributed_idle_ms_per_step as read  # noqa: F401
