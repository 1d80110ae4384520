"""Least time of the packed matmul kernels (attention and the LM head) of traced decode steps over their device time (%), at each step's rung."""
from layer_metrics import kernel_roofline as read  # noqa: F401
