#!/usr/bin/env python3
"""End-to-end smoke of the count-serving path on a TPU.

    python3 chip_smoke.py              # one chip: the whole serving path
    python3 chip_smoke.py --chips 4    # four chips: the sharded mesh path only

One chip: the paper's §4.3 Bernoulli simulation (4,000,000 transactions over
1,024 items, p_X = 0.04, p_Y = 0.01) is loaded into a ``CountServer``.  It
serves micro-batched flushes of 1-3-item keys, takes one append, and serves
a flush over base plus delta; a ``RuleServer.top_rules`` sweep then mines
the minority rules through level 2.  Every served count and the whole rule
set are compared bit for bit with a host reference that never calls the
kernel: one sorted row-id list per item, intersected in numpy.  The compiled
kernel must be in the flush program (``tpu_custom_call``) and must have
launched.

Four chips: the same data served by ``CountServer(shards=4, mesh=...)`` and
by a one-chip store, compared flush for flush, with the bytes each device
holds printed to show the rows were placed on all four.

The wall times printed are host-clock set-up and smoke timings, not device
metrics.  The script exits non-zero, and prints no result line, when JAX
finds no TPU or any phase fails.  The last line of standard output is the
result: ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Config:
    rows: int = 4_000_000
    items: int = 1024               # W = 32 words
    p_x: float = 0.04               # about 40 items a basket
    p_y: float = 0.01               # the rare class
    append_rows: int = 40_000       # stays a delta: below merge_ratio * base
    # an antecedent needs C1 >= ceil(theta * 4,040,000) = 91 class-1 rows;
    # a pair's expected C1 is about 65, so a few hundred pairs qualify
    theta: float = 2.25e-5
    min_conf: float = 0.01
    target_class: int = 1
    rounds: int = 4                 # flushes before the append
    batch: int = 64                 # requests per flush
    clients: int = 8
    pool: int = 512                 # distinct keys; repeats hit the cache


FULL = Config()


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    log(f"phase {name}: {time.perf_counter() - t0:.3f} s (host clock)")


# --------------------------------------------------------------------------
# host reference: numpy set logic, no encoder, no kernel
# --------------------------------------------------------------------------

def _member(a, b):
    """Mask of the entries of sorted ``a`` that occur in sorted ``b``."""
    import numpy as np

    if b.shape[0] == 0:
        return np.zeros(a.shape[0], bool)
    idx = np.minimum(np.searchsorted(b, a), b.shape[0] - 1)
    return b[idx] == a


class HostReference:
    """Per-class counts from one ascending row-id list per item."""

    def __init__(self, transactions, classes, n_items: int, n_classes: int):
        import numpy as np

        lens = np.fromiter(map(len, transactions), np.int64,
                           len(transactions))
        items = np.fromiter(itertools.chain.from_iterable(transactions),
                            np.int16, int(lens.sum()))
        rows = np.repeat(np.arange(len(transactions), dtype=np.int32), lens)
        # a stable sort keeps each item's rows ascending
        order = np.argsort(items, kind="stable")
        self.rows = rows[order]
        self.items = items[order]
        self.ends = np.cumsum(np.bincount(items, minlength=n_items))
        self.starts = self.ends - np.bincount(items, minlength=n_items)
        self.classes = np.asarray(classes, np.int64)
        self.n_items = n_items
        self.n_classes = n_classes

    def rows_of(self, item: int):
        return self.rows[self.starts[item]:self.ends[item]]

    def rows_with(self, itemset, n_rows: int):
        rows = None
        for a in sorted(set(itemset), key=lambda a: self.ends[a]
                        - self.starts[a]):
            r = self.rows_of(a)
            rows = r if rows is None else rows[_member(rows, r)]
        return rows[:rows.searchsorted(n_rows)]

    def counts(self, itemset, n_rows: int):
        import numpy as np

        return np.bincount(self.classes[self.rows_with(itemset, n_rows)],
                           minlength=self.n_classes)

    def rules(self, theta: float, min_conf: float, target: int,
              n_rows: int):
        """The minority rule set of ``minority_report``, level by level:
        antecedents with C1 >= ceil(theta * n_rows), kept at confidence
        C1 / (C1 + C0) >= min_conf, sorted (-confidence, -support,
        antecedent)."""
        import numpy as np

        from repro.core.incremental import ceil_count
        from repro.core.mra import Rule

        mc = ceil_count(theta * n_rows)
        live = self.rows < n_rows
        hit = live & (self.classes[self.rows] == target)
        c1 = np.bincount(self.items[hit], minlength=self.n_items)
        frequent = {(a,): int(c1[a]) for a in range(self.n_items)
                    if c1[a] >= mc}
        # pairs: C1 for every pair at once, from the target rows' matrix
        target_rows = np.flatnonzero(self.classes[:n_rows] == target)
        x1 = np.zeros((target_rows.shape[0], self.n_items), np.float32)
        x1[np.searchsorted(target_rows, self.rows[hit]), self.items[hit]] = 1
        pair_c1 = np.rint(x1.T @ x1).astype(np.int64)    # exact: < 2^24
        del x1
        singles = sorted(a for (a,) in frequent)
        level = {}
        for i, a in enumerate(singles):
            for b in singles[i + 1:]:
                if pair_c1[a, b] >= mc:
                    level[(a, b)] = int(pair_c1[a, b])
        depth = 1
        while level:
            depth += 1
            frequent.update(level)
            # apriori join of sorted k-sets sharing a (k-1)-prefix
            prev = sorted(level)
            cands = []
            for i, p in enumerate(prev):
                for q in prev[i + 1:]:
                    if p[:-1] != q[:-1]:
                        break
                    c = p + q[-1:]
                    if all(c[:j] + c[j + 1:] in level
                           for j in range(len(c))):
                        cands.append(c)
            level = {}
            for c in cands:
                n1 = int(self.counts(c, n_rows)[target])
                if n1 >= mc:
                    level[c] = n1
        rules = []
        for key in frequent:
            row = self.counts(key, n_rows)
            cnt = int(row[target])
            gcnt = int(row.sum()) - cnt
            conf = cnt / (cnt + gcnt) if (cnt + gcnt) else 0.0
            if conf >= min_conf:
                rules.append(Rule(antecedent=tuple(sorted(key, key=repr)),
                                  consequent=target, support=cnt / n_rows,
                                  confidence=conf, count=cnt, g_count=gcnt))
        rules.sort(key=lambda r: (-r.confidence, -r.support, r.antecedent))
        return rules, depth


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def _key_pool(cfg: Config, rng):
    return [tuple(rng.choice(cfg.items, size=int(rng.integers(1, 4)),
                             replace=False).tolist())
            for _ in range(cfg.pool)]


def _requests(cfg: Config, rng, pool):
    return [[pool[i] for i in rng.integers(0, cfg.pool,
                                           int(rng.integers(1, 4)))]
            for _ in range(cfg.batch)]


def _serve(server, cfg: Config, requests, tag: str):
    """Submit one round of requests and flush; the replies in request
    order."""
    tickets = [server.submit(f"client-{i % cfg.clients}", req)
               for i, req in enumerate(requests)]
    with phase(f"flush {tag}"):
        out = server.flush()
    check(set(out) == set(tickets), f"flush {tag}: tickets unanswered")
    return [out[t] for t in tickets]


def _check_against(ref, replies, requests, n_rows: int, tag: str) -> int:
    import numpy as np

    n = 0
    for req, got in zip(requests, replies):
        check(got.shape == (len(req), ref.n_classes),
              f"flush {tag}: reply shape {got.shape}")
        for key, row in zip(req, got):
            want = ref.counts(key, n_rows)
            check(np.array_equal(row, want),
                  f"flush {tag}: key {key} served {row.tolist()}, host "
                  f"reference {want.tolist()}")
            n += 1
    return n


def _bytes_in_use(jax):
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()]


def _device_report(jax) -> None:
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"device {d.id}: {d.platform} {d.device_kind}, bytes_in_use="
            f"{stats.get('bytes_in_use')}")


def run_one_chip(cfg: Config, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.data import bernoulli_db
    from repro.kernels.itemset_count import itemset_counts
    from repro.kernels.itemset_count.ops import MAX_KERNEL_WORDS
    from repro.serve import CountServer, RuleServer

    with phase("generate"):
        tx, y = bernoulli_db(cfg.rows, cfg.items, cfg.p_x, cfg.p_y, seed)
        tx_app, y_app = bernoulli_db(cfg.append_rows, cfg.items, cfg.p_x,
                                     cfg.p_y, seed + 1)
    with phase("load store"):
        server = CountServer(tx, classes=y, n_classes=2)
        ruler = RuleServer(server, target_class=cfg.target_class)
    store = server.store
    log(f"store: {store.resident}, {store.base_rows} unique rows of "
        f"{store.n_rows}, {store.vocab.size} items (W={store.vocab.n_words}),"
        f" {store.nbytes} bytes, serve block_k={server.batcher.block_k}")
    # the kernel route: neither use_kernel=False nor the wide-vocab jnp path
    check(store.use_kernel and store.vocab.n_words <= MAX_KERNEL_WORDS,
          "the store would count off the kernel route")
    n_total = cfg.rows + cfg.append_rows
    with phase("host reference"):
        ref = HostReference(tx + tx_app, np.concatenate([y, y_app]),
                            cfg.items, store.n_classes)

    rng = np.random.default_rng(seed + 2)
    pool = _key_pool(cfg, rng)
    served = 0
    for rnd in range(cfg.rounds):
        reqs = _requests(cfg, rng, pool)
        served += _check_against(ref, _serve(server, cfg, reqs, str(rnd)),
                                 reqs, cfg.rows, str(rnd))

    with phase("append"):
        ruler.append(tx_app, classes=y_app)
    check(store.delta_rows > 0, "the append was compacted: no delta left")
    before = store.kernel_launches
    reqs = _requests(cfg, rng, pool)
    served += _check_against(ref, _serve(server, cfg, reqs, "base+delta"),
                             reqs, n_total, "base+delta")
    check(store.kernel_launches - before == 2,
          "the base+delta flush did not launch once per segment")
    log(f"served {served} keys over {cfg.rounds + 1} flushes, all equal to "
        f"the host reference")

    # the flush program: the call the store makes for a base segment
    bk = server.batcher.block_k
    masks = jnp.zeros((bk, store.base.bits.shape[1]), jnp.uint32)
    flush_hlo = jax.jit(
        lambda t, g, w: itemset_counts(t, g, w, block_k=bk)).lower(
            store.base.bits, masks, store.base.weights).compile().as_text()
    check("tpu_custom_call" in flush_hlo,
          "no tpu_custom_call in the flush program: the kernel is not in it")

    # the mine's own launches: the counter read around the mine itself,
    # not around the antecedent resolve that top_rules runs after it
    mine_launches = []
    user_mine = server.mine

    def counted_mine(*args, **kwargs):
        before = obs.counter_total(obs.snapshot(), "kernel_launches_total")
        out = user_mine(*args, **kwargs)
        mine_launches.append(obs.counter_total(
            obs.snapshot(), "kernel_launches_total") - before)
        return out

    server.mine = counted_mine
    with phase("top_rules"):
        rules = ruler.top_rules(cfg.theta, cfg.min_conf)
    del server.mine
    check(len(mine_launches) == 1, f"top_rules ran {len(mine_launches)} mines")
    mine_launches = int(mine_launches[0])
    choice = server.last_backend_choice
    log(f"top_rules: {len(rules)} rules, backend {choice.name} "
        f"({choice.reason}); {mine_launches} kernel launches in the mine")
    check(mine_launches > 0, "the mine launched no kernel")
    with phase("host rule reference"):
        want, depth = ref.rules(cfg.theta, cfg.min_conf, cfg.target_class,
                                n_total)
    check(depth >= 2 and any(len(r.antecedent) >= 2 for r in want),
          f"the reference rule set stops at level {depth}")
    first_diff = next((i for i, (a, b) in enumerate(zip(rules, want))
                       if a != b), min(len(rules), len(want)))
    check(rules == want,
          f"top_rules: {len(rules)} rules served, {len(want)} in the host "
          f"reference; they differ from rule {first_diff} on")
    log(f"top_rules equal to the host reference: {len(want)} rules, "
        f"{sum(len(r.antecedent) >= 2 for r in want)} with 2+ items, "
        f"mined to level {depth}")
    stats = server.stats()["store"]
    check(stats["kernel_launches"] > 0, "store stats show no launch")
    log(f"store kernel_launches={stats['kernel_launches']}")
    _device_report(jax)


def run_four_chips(cfg: Config, seed: int) -> None:
    import jax
    import numpy as np

    from repro.data import bernoulli_db
    from repro.serve import CountServer

    check(len(jax.devices()) >= 4, "--chips 4 needs four devices")
    with phase("generate"):
        tx, y = bernoulli_db(cfg.rows, cfg.items, cfg.p_x, cfg.p_y, seed)
    with phase("load one-chip store"):
        single = CountServer(tx, classes=y, n_classes=2)
    # device 0 holds the one-chip store; what the sharded store adds to it
    # is compared with what it adds to the other devices
    in_use_single = _bytes_in_use(jax)
    mesh = jax.make_mesh((4,), ("data",))
    with phase("load sharded store"):
        sharded = CountServer(tx, classes=y, n_classes=2, shards=4,
                              mesh=mesh)
    rng = np.random.default_rng(seed + 2)
    pool = _key_pool(cfg, rng)
    served = 0
    for rnd in range(cfg.rounds):
        reqs = _requests(cfg, rng, pool)
        want = _serve(single, cfg, reqs, f"{rnd} one-chip")
        got = _serve(sharded, cfg, reqs, f"{rnd} sharded")
        for req, g, w in zip(reqs, got, want):
            check(np.array_equal(g, w),
                  f"flush {rnd}: sharded {g.tolist()} != one-chip "
                  f"{w.tolist()} for {req}")
            served += len(req)
    log(f"served {served} keys over {cfg.rounds} flushes: sharded equal to "
        f"one-chip")
    launches = sharded.stats()["store"]["kernel_launches"]
    check(launches > 0, "the sharded store launched no kernel")
    bits_d, _ = sharded.store._resident_placement()
    per_device = {s.device.id: int(s.data.nbytes)
                  for s in bits_d.addressable_shards}
    log(f"sharded: {launches} mesh launches; resident row bytes per device "
        f"{per_device}")
    check(len(per_device) == 4 and min(per_device.values())
          >= int(bits_d.nbytes) // 4,
          "the rows were not placed on all four devices")
    _device_report(jax)
    added = [now - then for now, then in zip(_bytes_in_use(jax),
                                             in_use_single)]
    log(f"sharded store: bytes_in_use added per device {added}")
    check(added[0] <= 2 * max(added[1:]),
          f"device 0 holds {added[0]} bytes more for the sharded store, the "
          f"others at most {max(added[1:])}: rows were kept on device 0 "
          f"beside the placement")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded mesh path and its one-chip "
                         "comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.roofline.peaks import peaks_for

    kind = devices[0].device_kind
    log(f"device kind: {kind}, {len(devices)} device(s); compile cache: "
        f"{enable_compile_cache()}")
    try:
        check(peaks_for(kind) is not None,
              f"{kind!r} is not in the peaks table (roofline/peaks.py)")
        if args.chips == 4:
            run_four_chips(FULL, args.seed)
        else:
            run_one_chip(FULL, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
