"""Percent of the traced window in which no device operation ran, over the rung cycle."""
from layer_metrics import device_idle as read  # noqa: F401
