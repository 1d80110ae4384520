"""Time of nq.page_in.put (fetched streams put on the device) inside the switches that page in, over their summed duration (%)."""
from functools import partial

from progtrace import switch_share

read = partial(switch_share, part="put")
