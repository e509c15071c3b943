"""How late the load generator submitted, 95th percentile over the window's
requests: each submit's time minus its due time (the benchmark's clock).
If it grows, the cell measures the generator, not the server."""
import numpy as np


def read(ctx):
    lag = ctx.get("gen_lag_ms")
    if lag is None or len(lag) == 0:
        return None
    v = np.sort(np.asarray(lag, np.float64))
    return float(v[max(0, int(np.ceil(0.95 * v.shape[0])) - 1)])
