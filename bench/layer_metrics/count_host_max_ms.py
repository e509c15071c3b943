"""The longest host time of one call into the count op in the window, in
ms: a ``kernel.count`` span less its ``kernel.wait`` child (the wait for
the device), so what is left is the call's preparation, dispatch and launch
record.  Nothing to read where the call is not split into child spans."""
from bench.spans import has, in_window, time_beneath


def read(ctx):
    if not has(ctx, "kernel.launch"):
        return None
    calls = in_window(ctx, "kernel.count")
    if not calls:
        return None
    wait = time_beneath(ctx, "kernel.wait", (s.span_id for s in calls))
    return max((s.t1 - s.t0) - wait.get(s.span_id, 0.0) for s in calls) * 1e3
