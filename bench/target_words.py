"""The words the targets of each count hold, recorded by the benchmark at
the call into the store.

``bench/roofline.py`` prices an ``itemset_count`` launch by the words its
targets hold.  While a traced window runs, :func:`recorded` wraps the
``counts_masks`` method of the driver's store (on the instance, for the
window only): before each call it records one instant span named
:data:`INSTANT` with

* ``mask_words``: the nonzero words of the (K, W) target block, the sum
  over targets of the words each holds (pad rows and rows of unknown
  items are all zero and add nothing);
* ``union_words``: the words that some target holds.

The instant sits beneath the span open on the calling thread, which is the
``serve.count`` span that records ``n_masks`` and encloses the call's
``kernel.count`` launches (base and delta share its targets).  An instant
has no length, so it names no idle gap and moves no span reader.  Outside
a traced window nothing is wrapped and nothing is computed.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Dict

import numpy as np

INSTANT = "bench.target_words"


def words_of(masks: np.ndarray) -> Dict[str, int]:
    """``mask_words`` and ``union_words`` of a (K, W) target block."""
    masks = np.asarray(masks)
    return {"mask_words": int(np.count_nonzero(masks)),
            "union_words": int(np.count_nonzero(masks.any(axis=0)))}


@contextmanager
def recorded(state: Dict, enabled: bool):
    """While the block runs, record the words of every target block that
    ``state["server"].store`` counts; nothing when not ``enabled`` or when
    the driver keeps no such store."""
    store = getattr(state.get("server"), "store", None)
    if not enabled or not hasattr(store, "counts_masks"):
        yield
        return
    from repro import obs

    count = store.counts_masks

    def counts_masks(masks, *args, **kwargs):
        if obs.TRACER.enabled:
            obs.TRACER.instant(INSTANT, words_of(masks))
        return count(masks, *args, **kwargs)

    store.counts_masks = counts_masks
    try:
        yield
    finally:
        del store.counts_masks
