"""Model FLOPs of the real rows of traced decode steps, this chip's share of the experts included, over their device time times the bf16 peak (%)."""
from layer_metrics import step_mfu as read  # noqa: F401
