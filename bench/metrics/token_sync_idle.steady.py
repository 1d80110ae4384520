"""Percent of the traced window in which no device operation ran and the innermost program span was nq.token_sync."""
from progtrace import token_sync_idle as read  # noqa: F401
