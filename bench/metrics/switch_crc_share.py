"""Time of nq.page_in.crc (the CRC-32 of each array read) inside the switches that page in, over their summed duration (%)."""
from functools import partial

from progtrace import switch_share

read = partial(switch_share, part="crc")
