"""Kernel launches per served flush over the window (registry
``kernel_launches_total / serve_flushes_total``): 2 while base and delta
launch apart."""
from bench.readings import counter_delta


def read(ctx):
    flushes = counter_delta(ctx, "serve_flushes_total")
    if flushes <= 0:
        return None
    return counter_delta(ctx, "kernel_launches_total") / flushes
