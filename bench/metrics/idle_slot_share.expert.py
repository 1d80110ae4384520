"""Closed-loop expert cell: percent of batch rows x decode steps that stamped no real token."""
from layer_metrics import idle_slot_share as read  # noqa: F401
