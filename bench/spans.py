"""What the readers of the program's own spans share: the spans of the
window, the time of named spans beneath others, and a nearest-rank
percentile.  Spans are the ring's ``Span`` objects (``name``, ``span_id``,
``parent_id``, ``t0``, ``t1``, ``attrs``), on the host's ``perf_counter``
clock like the window's ``t_open`` and ``t_close``."""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def spans(ctx: Dict) -> List:
    return ctx.get("spans") or []


def has(ctx: Dict, name: str) -> bool:
    """Whether the program records spans of this name at all (a program
    without them gives its readers nothing to read)."""
    return any(s.name == name for s in spans(ctx))


def in_window(ctx: Dict, name: str) -> List:
    """The spans (or instants) of this name that start inside the window."""
    lo = ctx.get("t_open", -math.inf)
    hi = ctx.get("t_close", math.inf)
    return [s for s in spans(ctx) if s.name == name and lo <= s.t0 <= hi]


def time_beneath(ctx: Dict, name: str, ancestors: Iterable[int]
                 ) -> Dict[int, float]:
    """Seconds of the spans called ``name`` beneath (at any depth) each of
    the spans whose ids are ``ancestors``: {ancestor id -> seconds}."""
    wanted = set(ancestors)
    by_id = {s.span_id: s for s in spans(ctx)}
    out: Dict[int, float] = {}
    for s in spans(ctx):
        if s.name != name:
            continue
        p = by_id.get(s.parent_id)
        while p is not None:
            if p.span_id in wanted:
                out[p.span_id] = out.get(p.span_id, 0.0) + (s.t1 - s.t0)
                break
            p = by_id.get(p.parent_id)
    return out


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """The ``ceil(q * n)``-th smallest value; None for no values."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q * len(v)) - 1)]
