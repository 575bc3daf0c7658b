"""Device time per decode step of the operations under a protection site's scope other than the ``ft_matmul`` kernel: casts, pads, fault grid, slice (ms).

Reported in the batch cell; moves ``out_tok_s``.  Read from the program's own
spans and scopes (``bench/program_trace.py``); silent where the program has none."""
from bench.program_trace import ft_wrap_device_ms as read  # noqa: F401
