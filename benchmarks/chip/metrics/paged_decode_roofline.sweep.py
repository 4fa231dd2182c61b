"""The sharded paged decode kernel's share of its roofline on chip 0: its least time from live tokens (heads divided over the model axis) over its device time, in %."""
from chipbench.readers import DECODE, roofline


def read(run):
    return roofline(run, DECODE)
