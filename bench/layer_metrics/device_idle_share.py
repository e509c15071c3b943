"""Share of the window in which no operation ran on the device: 1 minus
the union of device operation intervals over the window, from the
profiler trace."""
from bench.readings import idle_share


def read(ctx):
    return idle_share(ctx)
