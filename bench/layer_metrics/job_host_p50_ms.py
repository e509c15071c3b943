"""Host time of one ``CountServer.query`` job, median over the window's
jobs, in ms: each ``serve.query`` span less the ``kernel.wait`` time
beneath it (the waits for the device), so what is left is the keys, the
cache probe, the masks, the count op's host parts, the cache fill and the
reply.  Nothing to read where the query's steps have no spans."""
from bench.spans import has, in_window, nearest_rank, time_beneath


def read(ctx):
    if not has(ctx, "serve.keys"):
        return None
    jobs = in_window(ctx, "serve.query")
    wait = time_beneath(ctx, "kernel.wait", (s.span_id for s in jobs))
    return nearest_rank([((s.t1 - s.t0) - wait.get(s.span_id, 0.0)) * 1e3
                         for s in jobs], 0.5)
