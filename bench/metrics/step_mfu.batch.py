"""Model FLOPs of the real rows of traced decode steps over their device time times the bf16 peak (%), over the rung cycle."""
from layer_metrics import step_mfu as read  # noqa: F401
