"""Device time per decode step of the operations under the ``attn.latent`` scope: MLA's absorbed latent attention (q times W_uk, the scores over the latent cache, the softmax, the context and its product with W_uv) (ms).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  Read from
the program's own scopes (``bench/program_trace.py``); silent where no
operation carries the scope."""
from bench.program_trace import scoped_device_ms

SCOPE = "attn.latent"


def read(ctx):
    return scoped_device_ms(ctx, lambda scopes, op: SCOPE in scopes) or None
