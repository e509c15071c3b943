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


def launches(ctx: Dict) -> List[Tuple[int, int, int, int, int]]:
    """(n, k, w, c, n_real) of every ``kernel.count`` span of the window:
    ``k`` as launched, ``n_real`` the targets the caller asked for (the
    ``n_masks`` of the nearest enclosing span that records it; else k)."""
    spans = ctx.get("spans") or []
    by_id = {s.span_id: s for s in spans}
    out = []
    for s in spans:
        if s.name != "kernel.count":
            continue
        a = s.attrs
        real = int(a["k"])
        p = by_id.get(s.parent_id)
        while p is not None:
            if "n_masks" in p.attrs:
                real = min(real, int(p.attrs["n_masks"]))
                break
            p = by_id.get(p.parent_id)
        out.append((int(a["n"]), int(a["k"]), int(a["w"]), int(a["c"]),
                    real))
    return out


def roofline(ctx: Dict) -> Optional[float]:
    """Percent of the roofline over the window's launches, from their
    unpadded shapes and the kernel's device time; None with nothing to
    read.  Logs which bound sets it."""
    from bench import harness
    from bench.peaks import peaks_for
    from bench.roofline import roofline_share

    trace = ctx.get("trace")
    shapes = [(n, real, w, c) for n, _, w, c, real in launches(ctx)]
    if trace is None or not shapes or trace["kernel_s"] <= 0:
        return None
    share, bound = roofline_share(shapes, trace["kernel_s"],
                                  peaks_for(ctx["device_kind"]))
    harness.log(f"itemset_count roofline: {share:.3f}% of the {bound} "
                f"bound over {len(shapes)} launches")
    return share


def op_overhead(ctx: Dict) -> Optional[float]:
    trace = ctx.get("trace")
    if trace is None or trace["busy_s"] <= 0 or trace["kernel_s"] <= 0:
        return None
    return trace["other_ops_s"] / trace["busy_s"]


def idle_share(ctx: Dict) -> Optional[float]:
    trace = ctx.get("trace")
    return None if trace is None else trace["idle_share"]
