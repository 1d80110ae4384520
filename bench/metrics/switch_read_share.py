"""Time of nq.page_in.read (segment file reads) inside the switches that page in, over their summed duration (%)."""
from functools import partial

from progtrace import switch_share

read = partial(switch_share, part="read")
