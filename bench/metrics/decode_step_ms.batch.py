"""Mean device time (ms) of a decode-step module in the traced window, over the rung cycle."""
from layer_metrics import decode_step_ms as read  # noqa: F401
