"""Least time of the traced decode steps' held-expert matmuls over the time the ``ft_matmul_batched`` kernels took (%).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``.  The work
is the family's ``expert_calls`` at the expected routed pairs of each traced
step's active slots (``ctx.step_load``); the kernels are the
``%ft_matmul_batched.<n> = ... tpu_custom_call`` operations inside the decode
step's program.  Silent unless each step ran one kernel per expert matmul
(gate, up, down) of each MoE layer, or where the family counts no experts."""
from bench.layer_metrics import STEP_MODULE

KERNEL = r"^%ft_matmul_batched(\.\d+)? = .*tpu_custom_call"
MATMULS_PER_LAYER = 3


def read(ctx):
    expert_calls = getattr(ctx.family, "expert_calls", None)
    if expert_calls is None or not ctx.step_load:
        return None
    w = ctx.window
    steps = w.modules_matching(STEP_MODULE)
    kernels = w.ops_within(steps, KERNEL)
    n_moe = ctx.config["num_hidden_layers"] - ctx.config.get("first_k_dense_replace", 0)
    if not steps or len(kernels) != len(steps) * MATMULS_PER_LAYER * n_moe:
        return None
    per_step = [sum(c.least_s(ctx.peaks) for c in expert_calls(ctx.config, active))
                for active, _ in ctx.step_load]
    least = len(steps) * sum(per_step) / len(per_step)
    return 100.0 * least / sum(k.dur for k in kernels)
