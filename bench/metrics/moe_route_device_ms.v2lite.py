"""Device time per decode step of the operations under the ``moe.route`` scope: the router's softmax and top-k, the building of the dispatch and combine tensors, and the combine einsum (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  The
dispatch einsum is not here: XLA fuses it into the reshape of the experts'
operand, and a fusion carries its root's scope, ``moe.expert``, so its time
counts in ``ft_wrap_device_ms.v2lite``.  Read from the program's own scopes
(``bench/program_trace.py``); silent where no operation carries the scope."""
from bench.program_trace import scoped_device_ms

SCOPE = "moe.route"


def read(ctx):
    return scoped_device_ms(ctx, lambda scopes, op: SCOPE in scopes) or None
