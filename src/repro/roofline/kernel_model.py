"""Roofline model of the ``itemset_count`` Pallas kernel, per launch geometry.

The counting kernel is a (N, W)-bitmap x (K, W)-target containment sweep
with a per-class weighted reduction: for every (row, target) pair it ANDs
and compares W packed words, then accumulates C weight columns for the
contained pairs.  Per launch of geometry (N, K, W, C):

  bytes  = 4 * (N*W + N*C + K*W + K*C)      one pass over bitmap + weights,
                                            targets + the (K, C) result
  "FLOPs"= N*K * (2*W + C)                  W ANDs + W compares per pair,
                                            plus the C-column accumulate
                                            (integer ops priced as FLOPs at
                                            the VPU's int32 lane rate)

Predicted launch time on the chip the launch ran on is the perfect-overlap
roofline bound ``max(bytes/HBM_BW, flops/PEAK_FLOPS)`` with that chip's
entry in ``roofline.peaks`` (looked up by ``device_kind``).  No VPU integer
rate is published, so the op count is priced at the bf16 MXU peak — an
optimistic bound.  ``record_launch`` publishes measured wall time against
that prediction into the telemetry registry (``repro.obs``) so
``CountServer.stats()`` / the Prometheus export report a
measured-vs-predicted **efficiency ratio** per geometry.  A device kind the
peaks table does not know (the CPU among them) records launches and
measured time but no prediction, so it reports no ratio.
"""
from __future__ import annotations

import functools
import re
from typing import Optional, Tuple

from .peaks import ChipPeaks, local_peaks

_WORD_BYTES = 4


def kernel_flops(n: int, k: int, w: int, c: int) -> float:
    """Integer-op count of one containment sweep, priced as FLOPs."""
    return float(n) * float(k) * (2.0 * w + c)


def kernel_bytes(n: int, k: int, w: int, c: int) -> float:
    """HBM traffic of one sweep: bitmap + weights + targets + result."""
    return _WORD_BYTES * (float(n) * w + float(n) * c
                          + float(k) * w + float(k) * c)


def predicted_seconds(n: int, k: int, w: int, c: int,
                      peaks: ChipPeaks) -> float:
    """Perfect-overlap roofline bound for one launch on the chip ``peaks``
    describes."""
    return max(kernel_flops(n, k, w, c) / peaks.bf16_flops,
               kernel_bytes(n, k, w, c) / peaks.hbm_bytes_per_s)


@functools.lru_cache(maxsize=1)
def _launch_peaks() -> Optional[ChipPeaks]:
    """Peaks of the device the launches run on (fixed for the process)."""
    return local_peaks()


def geometry_label(n: int, k: int, w: int, c: int) -> str:
    """EXACT per-geometry label (debug/report use).  Telemetry records under
    :func:`geometry_bucket` instead — see below."""
    return f"n{n}_k{k}_w{w}_c{c}"


# -- geometry bucketing ------------------------------------------------------
#
# Telemetry labels and tuning-table keys are BUCKETIZED geometries: each
# dimension rounds UP to a power of two inside a clamped range, so however
# adversarial the query mix (one distinct N per append, one distinct K per
# query shape) the label set stays bounded and the metrics registry cannot
# grow without limit.  The roofline PREDICTION still uses the exact geometry
# — only the label under which it is aggregated is rounded.  A hard cap
# backstops the clamp: once ``MAX_GEOMETRY_BUCKETS`` distinct buckets exist,
# any new bucket collapses into the single ``GEOMETRY_OVERFLOW`` label.

_BUCKET_RANGES = ((128, 1 << 26),   # n: kernel pads rows to 128 anyway
                  (8, 1 << 20),     # k: kernel pads targets to 8
                  (1, 64),          # w: MAX_KERNEL_WORDS
                  (1, 16))          # c: class columns
MAX_GEOMETRY_BUCKETS = 256
GEOMETRY_OVERFLOW = "overflow"
_BUCKET_RE = re.compile(r"n(\d+)_k(\d+)_w(\d+)_c(\d+)")
_SEEN_BUCKETS: set = set()


def _bucket_dim(x: int, lo: int, hi: int) -> int:
    x = max(int(x), 1)
    p2 = 1 << (x - 1).bit_length()     # round up to a power of two
    return min(max(p2, lo), hi)


def geometry_bucket(n: int, k: int, w: int, c: int) -> str:
    """Bucketized geometry label: pow2-rounded, range-clamped dimensions."""
    bn, bk, bw, bc = (_bucket_dim(x, lo, hi)
                      for x, (lo, hi) in zip((n, k, w, c), _BUCKET_RANGES))
    return f"n{bn}_k{bk}_w{bw}_c{bc}"


def bucket_shape(bucket: str) -> Tuple[int, int, int, int]:
    """Parse ``"nN_kK_wW_cC"`` back to ``(n, k, w, c)`` (ValueError if not
    a geometry bucket — e.g. the overflow label)."""
    m = _BUCKET_RE.fullmatch(bucket)
    if m is None:
        raise ValueError(f"not a geometry bucket label: {bucket!r}")
    return tuple(int(g) for g in m.groups())  # type: ignore[return-value]


def _bucket_label(n: int, k: int, w: int, c: int) -> str:
    """Bucket label with the hard cardinality cap applied."""
    b = geometry_bucket(n, k, w, c)
    if b in _SEEN_BUCKETS:
        return b
    if len(_SEEN_BUCKETS) >= MAX_GEOMETRY_BUCKETS:
        return GEOMETRY_OVERFLOW
    _SEEN_BUCKETS.add(b)
    return b


def _reset_geometry_buckets() -> None:
    """Drop the seen-bucket cap state (tests only)."""
    _SEEN_BUCKETS.clear()


def count_launch(n: int, k: int, w: int, c: int) -> str:
    """Count one eager launch under its geometry bucket (with or without a
    measured time); returns the bucket label."""
    from ..obs import REGISTRY

    geom = _bucket_label(n, k, w, c)
    REGISTRY.counter("kernel_launches_total", geometry=geom).inc()
    return geom


def record_launch(n: int, k: int, w: int, c: int, seconds: float) -> None:
    """Publish one measured launch against the model: three counters per
    geometry BUCKET (launch count, measured seconds, predicted seconds) —
    the efficiency ratio is derived at snapshot time by
    ``repro.obs.kernel_efficiency``.  The prediction uses the exact
    geometry; only the aggregation label is bucketized (bounded label set,
    and the same keys the tuning table uses — closing the feedback loop in
    ``roofline.autotune.staleness_report``).  On a device kind without
    peaks the prediction counter is left alone, so no ratio is derived."""
    from ..obs import REGISTRY

    geom = count_launch(n, k, w, c)
    REGISTRY.counter("kernel_measured_s_total", geometry=geom).inc(seconds)
    peaks = _launch_peaks()
    if peaks is not None:
        REGISTRY.counter("kernel_predicted_s_total", geometry=geom).inc(
            predicted_seconds(n, k, w, c, peaks))
