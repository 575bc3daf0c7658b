"""Host time of the fault scan's device-to-host readbacks (``hyca.fault.scan.sync`` spans) per server step (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  Read from the program's own
spans and scopes (``bench/program_trace.py``); silent where the program has none."""
from bench.program_trace import scan_sync_ms_per_step as read  # noqa: F401
