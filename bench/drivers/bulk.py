"""Multitude-targeted counting: a closed loop of ``CountServer.query`` jobs
over the target list of the Minority-Report Algorithm's count step
(arXiv:1803.06632): the itemsets frequent in the minority class, each
counted in every class over every row.

Parameters of the mix (``bench/traffic/<mix>.json``):

* ``theta``, ``target_class`` and ``max_level``: the target list is every
  itemset of at most ``max_level`` items whose count in ``target_class``
  is at least ``theta`` times the rows, listed by the plain reference from
  the data the program serves (base and delta), in an order drawn from the
  seed;
* ``keys_per_job``: the targets of one job, taken from the list in turn;
* ``append``: whether the configuration's append is folded in during
  set-up (every job then counts base and delta);
* ``check_answers``: counts compared with the reference after the window.

Jobs go round the list; the count cache holds far fewer keys than the list,
so it never answers.  The window runs whole jobs: the last one starts
before ``seconds`` and the window closes when it returns, so the rate is
over all the work and all the time of the window.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from bench.generators import rows_between
from bench.harness import phase


def target_list(data: Dict, traffic: Dict, seed: int, n_rows: int,
                n_classes: int) -> list:
    """The minority-frequent itemsets of the mix over the first ``n_rows``
    rows, as tuples, in the seed's order."""
    from bench.reference import minority_frequent

    found = minority_frequent(data, n_rows, float(traffic["theta"]),
                              int(traffic["target_class"]),
                              int(traffic["max_level"]), n_classes)
    keys = sorted(found)
    order = np.random.default_rng([seed, 20]).permutation(len(keys))
    return [keys[i] for i in order.tolist()]


def setup(cfg: Dict, traffic: Dict, seed: int, data: Dict,
          seconds: float) -> Dict:
    from repro.serve import CountServer

    base = int(data["base_rows"])
    n_rows = int(data["row_ptr"].shape[0] - 1)
    n_classes = int(cfg["n_classes"])
    with phase("rows"):
        tx, y = rows_between(data, 0, base)
    with phase("load"):
        server = CountServer(tx, classes=y, n_classes=n_classes)
        del tx
    if traffic.get("append", True) and n_rows > base:
        with phase("append"):
            tx, y = rows_between(data, base, n_rows)
            server.append(tx, classes=y)
            del tx
    else:
        n_rows = base
    per_job = int(traffic["keys_per_job"])
    with phase("targets"):
        targets = target_list(data, traffic, seed, n_rows, n_classes)
        n_jobs = len(targets) // per_job
        if n_jobs < 2:
            raise ValueError(f"{len(targets)} targets make fewer than two "
                             f"jobs of {per_job}")
        jobs = [targets[i * per_job:(i + 1) * per_job]
                for i in range(n_jobs)]
    with phase("warm job"):
        # the job's shapes, compiled; the window reaches this job last
        server.query(jobs[-1])
    return {"server": server, "traffic": traffic, "seed": seed,
            "n_rows": n_rows, "n_classes": n_classes, "jobs": jobs}


def window(state: Dict, seconds: float) -> Dict:
    server = state["server"]
    jobs = state["jobs"]
    answers = []
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        answers.append(server.query(jobs[len(answers) % len(jobs)]))
    t_end = time.perf_counter()
    done = sum(a.shape[0] for a in answers)
    return {"answers": answers, "window_s": t_end - t_open,
            "attempted": done, "failed": 0, "jobs_done": len(answers)}


def end_to_end(state: Dict, obs: Dict) -> Dict:
    if obs["window_s"] <= 0 or not obs["answers"]:
        return {}
    return {"targets_per_s": obs["attempted"] / obs["window_s"]}


def layer_context(state: Dict, obs: Dict) -> Dict:
    return {"jobs": obs["jobs_done"], "window_s": obs["window_s"],
            "base_rows": state["server"].store.base_rows}


def release(state: Dict) -> None:
    server = state.pop("server", None)
    if server is not None:
        server.close()


def check(state: Dict, obs: Dict, data: Dict) -> Dict:
    """A sample of the window's counts, drawn from the seed, equal to the
    reference's; every job answered in full."""
    from bench.reference import from_data

    per_job = int(state["traffic"]["keys_per_job"])
    jobs = state["jobs"]
    short = sum(int(a.shape != (per_job, state["n_classes"]))
                for a in obs["answers"])
    total = len(obs["answers"]) * per_job
    rng = np.random.default_rng([state["seed"], 21])
    picks = rng.choice(total, min(total, int(state["traffic"]
                                              ["check_answers"])),
                       replace=False) if total else []
    ref = from_data(data, state["n_rows"], state["n_classes"])
    wrong = 0
    for p in sorted(picks):
        job, i = divmod(int(p), per_job)
        got = obs["answers"][job]
        key = jobs[job % len(jobs)][i]
        if got.shape[0] <= i or not np.array_equal(
                got[i], ref.counts(key, state["n_rows"])):
            wrong += 1
    return {"short_jobs": {"value": int(short), "limit": 0},
            "wrong_counts": {"value": int(wrong), "limit": 0},
            "nothing_compared": {"value": int(len(picks) == 0), "limit": 0}}
