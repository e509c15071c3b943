"""What the per-layer readers share: counter deltas over the window, the
program's kernel launches from its spans, and device shares from the
reduced trace."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def counter_delta(ctx: Dict, name: str, **labels) -> float:
    """Growth of a registry counter over the window (every label set, or
    the one ``labels`` names)."""
    def value(snap):
        sets = snap.get("counters", {}).get(name) or {}
        if not labels:
            return sum(sets.values())
        key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
        return sets.get(key, 0)
    return value(ctx["after"]) - value(ctx["before"])


def _count_calls(ctx: Dict):
    """Each ``kernel.count`` span of the window with the nearest enclosing
    span that records ``n_masks`` (None where none does)."""
    spans = ctx.get("spans") or []
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.name != "kernel.count":
            continue
        p = by_id.get(s.parent_id)
        while p is not None and "n_masks" not in p.attrs:
            p = by_id.get(p.parent_id)
        yield s, p


def _shape(s, p) -> Tuple[int, int, int, int, int]:
    a = s.attrs
    real = int(a["k"]) if p is None else \
        min(int(a["k"]), int(p.attrs["n_masks"]))
    return int(a["n"]), int(a["k"]), int(a["w"]), int(a["c"]), real


def launches(ctx: Dict) -> List[Tuple[int, int, int, int, int]]:
    """(n, k, w, c, n_real) of every ``kernel.count`` span of the window:
    ``k`` as launched, ``n_real`` the targets the caller asked for (the
    ``n_masks`` of the nearest enclosing span that records it; else k)."""
    return [_shape(s, p) for s, p in _count_calls(ctx)]


def priced_launches(ctx: Dict) -> List[Tuple[int, int, int, int,
                                             Optional[int], Optional[int]]]:
    """(n, n_real, w, c, held, touched) of every launch, as
    ``bench/roofline.py`` prices it: ``held`` and ``touched`` are the
    ``mask_words`` and ``union_words`` of the targets, from the nearest
    enclosing span that records ``n_masks``, or else from the benchmark's
    :data:`bench.target_words.INSTANT` beneath that span; both None
    (every target priced as holding all ``w`` words) where neither records
    them.  They are held to the launch's own width: a base narrower than
    the target block holds fewer words."""
    from bench.target_words import INSTANT

    recorded = {s.parent_id: s.attrs for s in ctx.get("spans") or []
                if s.name == INSTANT}
    out = []
    for s, p in _count_calls(ctx):
        n, _, w, c, real = _shape(s, p)
        held = touched = None
        if p is not None:
            words = p.attrs if "mask_words" in p.attrs else \
                recorded.get(p.span_id, {})
            if "mask_words" in words and "union_words" in words:
                held = min(int(words["mask_words"]), real * w)
                touched = min(int(words["union_words"]), w)
        out.append((n, real, w, c, held, touched))
    return out


def roofline(ctx: Dict) -> Optional[float]:
    """Percent of the roofline over the window's launches, priced by the
    words their targets hold, over the kernel's device time; None with
    nothing to read.  Logs which bound sets it, and how many launches had
    no record of their targets' words and were priced as dense."""
    from bench import harness
    from bench.peaks import peaks_for
    from bench.roofline import roofline_share

    trace = ctx.get("trace")
    shapes = priced_launches(ctx)
    if trace is None or not shapes or trace["kernel_s"] <= 0:
        return None
    share, bound = roofline_share(shapes, trace["kernel_s"],
                                  peaks_for(ctx["device_kind"]))
    dense = sum(1 for *_, held, _ in shapes if held is None)
    harness.log(f"itemset_count roofline: {share:.3f}% of the {bound} "
                f"bound over {len(shapes)} launches, {dense} of them "
                f"priced as dense (no record of their targets' words)")
    return share


def op_overhead(ctx: Dict) -> Optional[float]:
    trace = ctx.get("trace")
    if trace is None or trace["busy_s"] <= 0 or trace["kernel_s"] <= 0:
        return None
    return trace["other_ops_s"] / trace["busy_s"]


def idle_share(ctx: Dict) -> Optional[float]:
    trace = ctx.get("trace")
    return None if trace is None else trace["idle_share"]
