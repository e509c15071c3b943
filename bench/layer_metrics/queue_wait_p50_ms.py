"""How long a request sat in the batcher's queue, median over the
window's requests, in ms: from its ``serve.submit`` instant to the start of
the first ``serve.dedup`` span after it (the server lock orders the two,
and a flush's ``take()`` drains every pending request)."""
import bisect

from bench.spans import in_window, nearest_rank, spans


def read(ctx):
    drains = sorted(s.t0 for s in spans(ctx) if s.name == "serve.dedup"
                    and s.attrs.get("n_requests", 1) > 0)
    waits = []
    for s in in_window(ctx, "serve.submit"):
        i = bisect.bisect_left(drains, s.t0)
        if i < len(drains):
            waits.append((drains[i] - s.t0) * 1e3)
    return nearest_rank(waits, 0.5)
