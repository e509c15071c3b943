"""Roofline analysis from a compiled dry-run artifact.

Three terms per (arch × shape × mesh) cell, all in seconds-per-step on the
TARGET hardware (the TPU v5e entry of ``roofline.peaks``; compiled only):

  compute    = HLO_FLOPs_per_device            / PEAK_FLOPS
  memory     = HLO_bytes_accessed_per_device   / HBM_BW
  collective = Σ_ops ring_bytes_on_wire(op)    / LINK_BW

``cost_analysis()`` of the SPMD-partitioned module is already per-device
(verified empirically).  Collective bytes are NOT in cost_analysis, so we
parse the post-partitioning HLO text: every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute line carries the per-device
result shape and an iota ``replica_groups=[G,S]<=[N]`` (group size S); the
ring model converts result bytes to bytes-on-the-wire per device.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .peaks import PEAKS

# --- target hardware constants (the TPU v5e entry of roofline.peaks) -------
_TARGET = PEAKS["TPU v5 lite"]
PEAK_FLOPS = _TARGET.bf16_flops
HBM_BW = _TARGET.hbm_bytes_per_s
LINK_BW = 50e9           # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(segment: str, adjust_bf16_upcast: bool = False) -> int:
    """Sum byte sizes of all typed shapes in an HLO text segment.

    ``adjust_bf16_upcast``: XLA:CPU's float-normalization pass upcasts bf16
    compute (and therefore the collectives this container compiles) to f32;
    on the TPU target they stay bf16.  The jaxpr-level values are verified
    bf16, so f32 payloads are counted at 2 bytes/element under this flag.
    """
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        width = _DTYPE_BYTES[dt]
        if adjust_bf16_upcast and dt == "f32":
            width = 2
        total += n * width
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=lambda: defaultdict(int))
    result_bytes: Dict[str, int] = field(default_factory=lambda: defaultdict(int))
    wire_bytes: Dict[str, float] = field(default_factory=lambda: defaultdict(float))

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    def as_dict(self) -> dict:
        return {"counts": dict(self.counts),
                "result_bytes": dict(self.result_bytes),
                "wire_bytes": {k: float(v) for k, v in self.wire_bytes.items()},
                "total_wire_bytes": float(self.total_wire_bytes)}


def collective_bytes(hlo_text: str,
                     adjust_bf16_upcast: bool = True) -> CollectiveStats:
    """Per-device bytes-on-wire per collective kind (ring cost model)."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # result-producing collective op lines look like:  %x = TYPE[...] all-reduce(...)
        m = re.match(r"%?[\w.\-]+ = (.+?) (" + "|".join(_COLLECTIVES) + r")\(",
                     stripped)
        if not m:
            continue
        result_seg, kind = m.group(1), m.group(2)
        # `-start` variants duplicate with `-done`; count starts only
        if stripped.startswith("%" ) and ("-done" in stripped.split("=")[0]):
            continue
        rbytes = _shape_bytes(result_seg, adjust_bf16_upcast=adjust_bf16_upcast)
        n = _group_size(stripped)
        if kind == "collective-permute":
            # pairwise op: identified by source_target_pairs, no replica_groups
            n = 2 if "source_target_pairs" in stripped else n
        if n <= 1:
            continue
        frac = (n - 1) / n
        if kind == "all-reduce":
            wire = 2.0 * rbytes * frac
        elif kind == "all-gather":
            wire = rbytes * frac                  # result is the gathered (big) shape
        elif kind == "reduce-scatter":
            wire = rbytes * (n - 1)               # result is the scattered shard
        elif kind == "all-to-all":
            wire = rbytes * frac
        else:  # collective-permute
            wire = rbytes
        stats.counts[kind] += 1
        stats.result_bytes[kind] += rbytes
        stats.wire_bytes[kind] += wire
    return stats


@dataclass
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device
    wire_bytes: float            # per device
    collectives: CollectiveStats
    model_flops: float = 0.0     # analytic useful FLOPs per device
    n_devices: int = 1

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Perfect-overlap lower bound: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful FLOPs / (step_time * peak) — the MFU-at-roofline score."""
        t = self.step_time
        return self.model_flops / (t * PEAK_FLOPS) if t else 0.0

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "bytes_per_device": self.bytes_accessed,
            "wire_bytes_per_device": self.wire_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops_per_device": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.collectives.as_dict(),
        }


# XLA:CPU float-normalization upcasts bf16 HBM traffic to f32; the TPU target
# keeps bf16, so 'bytes accessed' from this container over-counts ~2x on
# bf16-dominant models.  Collectives are corrected per-op by dtype (above);
# the aggregate memory term uses this documented scalar.
MEM_BF16_UPCAST_ADJUST = 0.5


def analyze(compiled, model_flops_total: float, n_devices: int,
            mem_adjust: float = MEM_BF16_UPCAST_ADJUST) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    stats = collective_bytes(compiled.as_text())
    return Roofline(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)) * mem_adjust,
        wire_bytes=stats.total_wire_bytes,
        collectives=stats,
        model_flops=model_flops_total / n_devices,
        n_devices=n_devices,
    )


def model_flops(cfg, shape) -> float:
    """Analytic useful FLOPs per step: 6·N_active·D for training,
    2·N_active·D for prefill, 2·N_active·B per decoded token (+attention reads
    are bytes, not FLOPs — attention matmul FLOPs added explicitly)."""
    n_active = cfg.n_active_params()
    tokens = shape.seq_len * shape.global_batch
    # attention score+value matmul FLOPs (causal => /2)
    attn = 0.0
    n_attn_layers = sum(1 for i in range(cfg.n_layers)
                        if cfg.layer_kind(i) == "attn")
    if cfg.n_heads:
        h, dh = cfg.n_heads, cfg.d_head
        if shape.kind in ("train", "prefill"):
            attn = (2.0 * tokens * shape.seq_len * h * dh * 2 / 2) * n_attn_layers
        else:  # decode: 1 new token vs seq_len cache
            attn = (2.0 * shape.global_batch * shape.seq_len * h * dh * 2) * n_attn_layers
    if shape.kind == "train":
        return 6.0 * n_active * tokens + 3.0 * attn
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens + attn
    return 2.0 * n_active * shape.global_batch + attn
