"""Least time of the packed matmul kernels of traced decode steps over their device time (%)."""
from layer_metrics import kernel_roofline as read  # noqa: F401
