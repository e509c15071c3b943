"""Open-loop count serving: Poisson arrivals of small requests from many
clients, served by ``CountServer(async_flush=True)`` through
``submit_async`` and ``CountFuture.result``.

Parameters of the mix (``bench/traffic/<mix>.json``):

* ``rate_per_s``: the fixed arrival rate;
* ``keys_per_request`` and ``items_per_key``: ``[lo, hi]``, uniform;
* ``key_pool`` and ``zipf_theta``: a pool of distinct keys, requested by a
  Zipf law over their ranks;
* ``clients``: client ids, assigned round robin;
* ``append``: whether the configuration's append is folded in during
  set-up (every flush then counts base and delta);
* ``prefill_keys``: the hottest keys counted once in set-up, which fills the
  cache and compiles every target block a flush can launch;
* ``warm_k_blocks``: target blocks of ``256 * j`` keys, j = 1 .. this,
  counted in set-up (the shapes a flush can launch);
* ``warm_seconds``: open-loop traffic before the window, at the same rate;
* ``check_answers``: answers compared with the reference after the window.

Each request is timed from the time it was due to the time its future
returned.  One thread submits on the schedule; another waits on the futures
in order of submission (a flush answers all pending requests at once, so
they complete in that order).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Dict

import numpy as np

from bench import keys as K
from bench.generators import rows_between
from bench.harness import phase

RESULT_WAIT_S = 60.0      # a minute past the close for the last answers


def build_requests(state: Dict, rng: np.random.Generator,
                    seconds: float):
    t = state["traffic"]
    arrivals = K.poisson_arrivals(rng, float(t["rate_per_s"]), seconds)
    lo, hi = t["keys_per_request"]
    ranks, offs = K.requests(rng, state["zipf"], arrivals.shape[0], lo, hi)
    pool = state["pool_tuples"]
    reqs = [[pool[r] for r in ranks[offs[i]:offs[i + 1]].tolist()]
            for i in range(arrivals.shape[0])]
    return arrivals, reqs


def setup(cfg: Dict, traffic: Dict, seed: int, data: Dict,
          seconds: float) -> Dict:
    from repro.serve import CountServer

    base = int(data["base_rows"])
    n_rows = int(data["row_ptr"].shape[0] - 1)
    with phase("rows"):
        tx, y = rows_between(data, 0, base)
    with phase("load"):
        server = CountServer(tx, classes=y,
                             n_classes=int(cfg["n_classes"]),
                             async_flush=True)
        del tx
    if traffic.get("append", True) and n_rows > base:
        with phase("append"):
            tx, y = rows_between(data, base, n_rows)
            server.append(tx, classes=y)
            del tx
    else:
        n_rows = base
    with phase("key pool"):
        rng = np.random.default_rng([seed, 10])
        lo, hi = traffic["items_per_key"]
        pool = K.distinct_itemsets(rng, int(traffic["key_pool"]),
                                   int(data["n_items"]), lo, hi)
        state = {"server": server, "traffic": traffic, "seed": seed,
                 "n_rows": n_rows,
                 "pool_tuples": K.as_tuples(pool),
                 "zipf": K.Zipf(pool.shape[0],
                                float(traffic["zipf_theta"])),
                 "base_rows": server.store.base_rows}
    # every target block a flush can launch, over base and delta, and the
    # cache filled with the hottest keys
    with phase("prefill"):
        block = server.batcher.block_k
        done = 0
        hot = state["pool_tuples"]
        for j in range(1, int(traffic["warm_k_blocks"]) + 1):
            server.query(hot[done:done + block * j])
            done += block * j
        top = int(traffic["prefill_keys"])
        step = block * int(traffic["warm_k_blocks"])
        while done < top:
            server.query(hot[done:min(top, done + step)])
            done += step
    warm = float(traffic.get("warm_seconds", 0.0))
    if warm > 0:
        with phase("warm traffic"):
            arrivals, reqs = build_requests(state, np.random.default_rng(
                [seed, 11]), warm)
            drive(server, arrivals, reqs, int(traffic["clients"]))
    # the window's own schedule, built before it opens
    state["arrivals"], state["requests"] = build_requests(
        state, np.random.default_rng([seed, 12]), seconds)
    return state


def drive(server, arrivals, reqs, clients: int) -> Dict:
    """Submit ``reqs`` at ``t_open + arrivals`` and collect every answer."""
    n = len(reqs)
    submitted = np.zeros(n)
    done = np.full(n, np.nan)
    answers = [None] * n
    handoff: "queue.SimpleQueue" = queue.SimpleQueue()
    ids = [f"client-{i}" for i in range(clients)]

    def collect():
        while True:
            i, fut = handoff.get()
            if i < 0:
                return
            while True:
                try:
                    answers[i] = fut.result(0.25)
                    done[i] = time.perf_counter()
                    break
                except TimeoutError:
                    if closed[0] is not None and time.perf_counter() > \
                            closed[0] + RESULT_WAIT_S:
                        break               # never answered: failed
                except Exception:           # a failed flush: failed
                    break

    closed = [None]
    collector = threading.Thread(target=collect, name="bench-collect")
    collector.start()
    t_open = time.perf_counter()
    due = t_open + arrivals
    try:
        for i in range(n):
            d = due[i]
            now = time.perf_counter()
            if d > now:
                time.sleep(d - now)
            submitted[i] = time.perf_counter()
            handoff.put((i, server.submit_async(ids[i % clients], reqs[i])))
    finally:
        closed[0] = time.perf_counter()
        handoff.put((-1, None))
        collector.join()
    return {"t_open": t_open, "due": due, "submitted": submitted,
            "done": done, "answers": answers}


def window(state: Dict, seconds: float) -> Dict:
    reqs = state["requests"]
    out = drive(state["server"], state["arrivals"], reqs,
                 int(state["traffic"]["clients"]))
    answered = ~np.isnan(out["done"])
    out["attempted"] = len(reqs)
    out["failed"] = int(len(reqs) - answered.sum())
    out["latency_ms"] = (out["done"][answered] - out["due"][answered]) * 1e3
    out["lag_ms"] = (out["submitted"] - out["due"]) * 1e3
    return out


def nearest_rank(values: np.ndarray, p: float) -> float:
    """The ``ceil(p * n)``-th smallest value."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, int(np.ceil(p * v.shape[0])) - 1)])


def end_to_end(state: Dict, obs: Dict) -> Dict:
    lat = obs["latency_ms"]
    if lat.size == 0:
        return {}
    return {"count_p50_ms": nearest_rank(lat, 0.50),
            "count_p95_ms": nearest_rank(lat, 0.95)}


def layer_context(state: Dict, obs: Dict) -> Dict:
    return {"gen_lag_ms": obs["lag_ms"], "base_rows": state["base_rows"],
            "n_requests": obs["attempted"]}


def release(state: Dict) -> None:
    server = state.pop("server", None)
    if server is not None:
        server.close()
        del server
    state["pool_tuples"] = None


def check(state: Dict, obs: Dict, data: Dict) -> Dict:
    """Every request answered; a sample of answers drawn from the seed
    equal to the reference's counts at the served version."""
    from bench.reference import from_data

    reqs = state["requests"]
    answered = [i for i, a in enumerate(obs["answers"]) if a is not None]
    rng = np.random.default_rng([state["seed"], 13])
    n_check = min(int(state["traffic"]["check_answers"]), len(answered))
    picks = rng.choice(len(answered), n_check, replace=False) \
        if n_check else []
    ref = from_data(data, state["n_rows"])
    memo: Dict[tuple, np.ndarray] = {}
    wrong = 0
    compared = 0
    for j in sorted(picks):
        i = answered[j]
        got = np.asarray(obs["answers"][i])
        if got.shape != (len(reqs[i]), ref.n_classes):
            wrong += len(reqs[i])
            continue
        for key, row in zip(reqs[i], got):
            if key not in memo:
                memo[key] = ref.counts(key, state["n_rows"])
            compared += 1
            if not np.array_equal(row, memo[key]):
                wrong += 1
    return {"unanswered": {"value": int(obs["failed"]), "limit": 0},
            "wrong_counts": {"value": int(wrong), "limit": 0},
            "nothing_compared": {"value": int(compared == 0), "limit": 0}}
