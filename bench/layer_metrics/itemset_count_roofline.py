"""Percent of the roofline of the ``itemset_count`` kernel: the least time
of every launch in the window, max(ops / int32 ceiling, bytes / HBM),
from its unpadded shapes, over the kernel's device time in the trace."""
from bench.readings import roofline


def read(ctx):
    return roofline(ctx)
