"""Mean number of targets a flush asked the base segment to count: the
uncached keys of each base-segment ``kernel.count`` span (its enclosing
``serve.count`` span's ``n_masks``), padding left out."""
from bench.readings import launches


def read(ctx):
    base = [real for n, _, _, _, real in launches(ctx)
            if n == ctx.get("base_rows")]
    return None if not base else sum(base) / len(base)
