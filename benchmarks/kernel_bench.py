"""Counting-kernel microbenchmarks + §3.1-optimization ablation.

  * itemset_counts (Pallas, interpret on CPU) vs pure-jnp oracle across
    (N, K, W) — derived column carries achieved counting throughput and the
    TPU-target roofline estimate for the same tile schedule;
  * GFP work-counter ablation (conditional trees built / nodes visited) with
    and without data reduction (#4) and vs classic FP-growth — the paper's
    O(1)-checks argument, quantified.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.core import FPTree, GFPStats, ItemOrder, TISTree, gfp_growth, mine_frequent
from repro.data import bernoulli_db
from repro.kernels.itemset_count import itemset_counts, itemset_counts_ref
from repro.roofline.peaks import PEAKS

from .common import Row, timeit


def _kernel_rows() -> List[Row]:
    import jax.numpy as jnp

    rows: List[Row] = []
    rng = np.random.default_rng(0)
    for n, k, w, c in ((4096, 256, 4, 2), (16384, 512, 4, 2), (65536, 1024, 8, 2)):
        tx = jnp.asarray(rng.integers(0, 2 ** 32, (n, w), dtype=np.uint32)
                         & rng.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
        tgt = np.zeros((k, w), np.uint32)
        for i in range(k):
            b = rng.integers(0, 32 * w, size=3)
            for x in b:
                tgt[i, x >> 5] |= np.uint32(1) << np.uint32(x & 31)
        tgt = jnp.asarray(tgt)
        wts = jnp.asarray(rng.integers(0, 3, (n, c)).astype(np.int32))

        out_ref = itemset_counts_ref(tx, tgt, wts).block_until_ready()
        us_ref = timeit(lambda: itemset_counts_ref(tx, tgt, wts).block_until_ready())
        out_k = itemset_counts(tx, tgt, wts).block_until_ready()
        us_k = timeit(lambda: itemset_counts(tx, tgt, wts).block_until_ready())
        assert (np.asarray(out_ref) == np.asarray(out_k)).all()

        # v5e estimate: the kernel streams N*W words once per K-tile and
        # does N*K*W uint32 ops + N*K*C MACs (VPU).
        v5e = PEAKS["TPU v5 lite"]
        bytes_hbm = n * w * 4 * max(1, k // 256) + k * w * 4 + n * c * 4
        ops = n * k * (w + c)
        t_mem = bytes_hbm / v5e.hbm_bytes_per_s
        t_cmp = ops / (v5e.bf16_flops / 2)  # VPU int ops, not MXU — /2
        tag = f"kernel[N={n},K={k},W={w}]"
        rows.append((f"{tag}/jnp_oracle", us_ref, f"containments={n * k}"))
        rows.append((f"{tag}/pallas_interpret", us_k,
                     f"v5e_roofline_est_us={max(t_mem, t_cmp) * 1e6:.1f}"))
    return rows


def _gfp_ablation_rows() -> List[Row]:
    rows: List[Row] = []
    tx, _ = bernoulli_db(4000, 40, p_x=0.2, p_y=0.0, seed=3)
    counts = {}
    for t in tx:
        for a in set(t):
            counts[a] = counts.get(a, 0) + 1
    order = ItemOrder.from_counts(counts)
    tree = FPTree.build(tx, order)
    min_count = 60  # low enough that pairs/triples are frequent
    freq = mine_frequent(tx, min_count)
    targets = [k for k in freq if len(k) >= 2][:400]
    assert targets, "ablation needs multi-item targets" 

    for reduce_items, label in ((True, "gfp_with_datareduction"),
                                (False, "gfp_no_datareduction")):
        tis = TISTree(order)
        for t in targets:
            tis.insert(t, target=True)
        t0 = time.perf_counter()
        stats = gfp_growth(tis, tree, use_data_reduction=reduce_items)
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"gfp_ablation/{label}", us,
                     f"ctrees={stats.conditional_trees};"
                     f"consults={stats.header_consults}"))

    t0 = time.perf_counter()
    mine_frequent(tx, min_count)
    us_full = (time.perf_counter() - t0) * 1e6
    rows.append(("gfp_ablation/full_fpgrowth_baseline", us_full,
                 f"itemsets={len(freq)};targets={len(targets)}"))
    return rows


def run() -> List[Row]:
    return _kernel_rows() + _gfp_ablation_rows()
