"""Device time of every operation other than the ``itemset_count`` kernel
(the whole-bitmap pad and transpose among them) over device busy time,
from the profiler trace of the window."""
from bench.readings import op_overhead


def read(ctx):
    return op_overhead(ctx)
