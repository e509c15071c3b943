"""How long a submit waited for the count server's lock, 95th percentile
over the window's requests, in ms: the ``wait_ms`` of each
``serve.submit`` instant, from the call into ``submit_async`` to the
enqueue.  A flush holds the lock through its device sync."""
from bench.spans import in_window, nearest_rank


def read(ctx):
    return nearest_rank([s.attrs["wait_ms"]
                         for s in in_window(ctx, "serve.submit")
                         if "wait_ms" in s.attrs], 0.95)
