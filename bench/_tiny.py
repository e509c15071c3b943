"""Sizes at which the tests under ``bench/`` run each cell on the CPU (the
kernel in interpret mode): the cells' own configurations and mixes, cut
down, and a run of a few seconds."""

TINY = {
    "sim4m-serve": {
        "config": {"rows": 3000, "items": 64, "append_rows": 300},
        "traffic": {"key_pool": 3000, "prefill_keys": 600,
                    "warm_k_blocks": 2, "rate_per_s": 40,
                    "warm_seconds": 0.5, "check_answers": 100}},
    "sim4m-bulk": {
        "config": {"rows": 3000, "items": 64, "append_rows": 300,
                   "p_x": 0.2, "p_y": 0.1},
        "traffic": {"theta": 1e-3, "keys_per_job": 256,
                    "check_answers": 50}},
}

SECONDS = 1.5
