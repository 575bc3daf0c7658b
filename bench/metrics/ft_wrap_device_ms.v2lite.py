"""Device time per decode step of the operations under a protection site's scope other than the ``ft_matmul`` and ``ft_matmul_batched`` kernels: casts, pads, fault grid, slice, and the dispatch einsum that XLA fuses into the experts' operand (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  Read from
the program's own spans and scopes (``bench/program_trace.py``); silent where
the program has none."""
import re

from bench import layer_metrics
from bench.program_trace import SITES, scoped_device_ms

KERNELS = (re.compile(layer_metrics.KERNEL), re.compile(r"^%ft_matmul_batched(\.\d+)? = .*tpu_custom_call"))


def read(ctx):
    return scoped_device_ms(ctx, lambda scopes, op: bool(set(scopes) & SITES)
                            and not any(k.search(op.name) for k in KERNELS))
