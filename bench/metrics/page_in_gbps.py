"""Ledger page-in bytes of upgrade switches over their wall time (GB/s)."""
from layer_metrics import page_in_gbps as read  # noqa: F401
