"""Percent of the traced window in which no device operation ran."""
from layer_metrics import device_idle as read  # noqa: F401
