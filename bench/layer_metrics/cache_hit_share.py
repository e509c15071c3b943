"""Share of the count cache's lookups in the window that hit (registry
``cache_hits_total / (hits + misses)`` of the ``CountCache``)."""
from bench.readings import counter_delta


def read(ctx):
    hits = counter_delta(ctx, "cache_hits_total", cache="CountCache")
    misses = counter_delta(ctx, "cache_misses_total", cache="CountCache")
    return None if hits + misses <= 0 else hits / (hits + misses)
