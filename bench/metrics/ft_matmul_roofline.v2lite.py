"""Least time of the decode steps' protected matmuls over the time the ``ft_matmul`` kernels took (%).

Reported in the deepseek-v2-lite batch cell; moves ``out_tok_s``."""
from bench.layer_metrics import ft_matmul_roofline as read  # noqa: F401
