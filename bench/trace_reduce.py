"""From a JAX profiler trace of the window to device metrics.

* busy: the union of the intervals in which an operation ran on a device,
  clipped to the window (the benchmark's ``bench.window`` annotation), and
  averaged over the devices that ran any;
* idle share: 1 - busy / window;
* device time by operation name, and the time of the kernel's operations;
* idle gaps: the stretches of the window with nothing on device 0, each
  named by the innermost host span open at its middle (the program's
  spans, moved onto the trace's clock, or the benchmark's own
  annotations).

Only ``jax.profiler.ProfileData`` reads the file: no other dependency.
"""
from __future__ import annotations

import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW = "bench.window"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def short_name(name: str) -> str:
    """An HLO op's trace name without layouts, ``%`` and attributes:
    ``itemset_count.1 = s32[256,2] custom-call(u32[32,200704] tx_bits_t.1,
    ...)``."""
    s = name
    while True:
        t = re.sub(r"\{[^{}]*\}", "", s)
        if t == s:
            break
        s = t
    s = s.replace("%", "")
    cut = s.find("), ")
    return s[:cut + 1] if cut >= 0 else s


def _detail(event) -> str:
    """The event's name and the string values of its stats (the HLO op,
    its module, its long name), where a kernel's name may sit."""
    parts = [event.name]
    try:
        for key, value in event.stats:
            if isinstance(value, str):
                parts.append(f"{key}={value}")
    except (TypeError, ValueError):
        pass
    return " ".join(parts)


def load(path: str) -> Dict:
    """Device op events per device plane, and host events, from an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    return from_planes(prof.planes)


def from_planes(planes: Iterable) -> Dict:
    devices: Dict[str, List[Tuple[str, str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            pick = next((lines[n] for n in OPS_LINES if n in lines), None)
            if pick is None:
                pick = next((lines[n] for n in MODULE_LINES if n in lines),
                            None)
            if pick is None:
                continue
            devices[plane.name] = [
                (short_name(ev.name), _detail(ev), float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns))
                for ev in pick.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns)
                                     + float(ev.duration_ns)))
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that ``busy`` (merged) leaves free."""
    out = []
    t = lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost (latest started) span open at ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or a > best[1]):
            best = (name, a)
    return best[0] if best else "none"


def reduce(events: Dict, kernel: str, *, window: Optional[Interval] = None,
           spans: Sequence[Tuple[str, float, float]] = ()) -> Dict:
    """Device metrics of the window.  ``spans`` are extra host spans, on
    the trace's clock, that may name idle gaps."""
    if window is None:
        marks = [(a, b) for name, a, b in events["host"] if name == WINDOW]
        if marks:
            window = marks[0]
        else:
            ends = [(a, b) for evs in events["devices"].values()
                    for _, _, a, b in evs]
            if not ends:
                raise ValueError("the trace holds no device operation")
            window = (min(a for a, _ in ends), max(b for _, b in ends))
    lo, hi = window
    by_name: Dict[str, float] = {}
    kernel_ns = 0.0
    busy_ns = []
    first = None
    for plane in sorted(events["devices"]):
        evs = [(n, d, a, b) for n, d, a, b in events["devices"][plane]
               if b > lo and a < hi]
        if not evs:
            continue
        merged = union(clip(((a, b) for _, _, a, b in evs), lo, hi))
        busy_ns.append(sum(b - a for a, b in merged))
        if first is None:
            first = merged
        for name, detail, a, b in evs:
            dur = min(b, hi) - max(a, lo)
            by_name[name] = by_name.get(name, 0.0) + dur
            if kernel in detail:
                kernel_ns += dur
    if not busy_ns:
        raise ValueError("no device operation ran in the window")
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    total_ops_ns = sum(by_name.values())
    host_spans = list(spans) + [s for s in events["host"] if s[0] != WINDOW]
    idle = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "kernel_s": kernel_ns / 1e9 / len(busy_ns),
        "ops_s": total_ops_ns / 1e9 / len(busy_ns),
        "other_ops_s": (total_ops_ns - kernel_ns) / 1e9 / len(busy_ns),
        "breakdown": {
            "device_ops": [[n, s / 1e9] for n, s in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[label_at((a + b) / 2, host_spans), (b - a) / 1e9]
                          for a, b in idle],
        },
    }


def reduce_file(path: str, ctx: Dict, kernel: str) -> Dict:
    """:func:`reduce` of a trace file, with the program's spans of the run
    (``ctx["spans"]``, on the host's ``perf_counter`` clock) moved onto the
    trace's clock by the window's annotation, which opens at
    ``ctx["t_open"]``."""
    events = load(path)
    for plane, evs in sorted(events["devices"].items()):
        print(f"trace: {plane}: {len(evs)} device events", file=sys.stderr)
    marks = [(a, b) for name, a, b in events["host"] if name == WINDOW]
    spans = []
    if marks and ctx.get("spans"):
        shift = marks[0][0] - ctx["t_open"] * 1e9
        spans = [(s.name, s.t0 * 1e9 + shift, s.t1 * 1e9 + shift)
                 for s in ctx["spans"] if s.t1 > s.t0]
    return reduce(events, kernel, spans=spans)
