"""Roofline-driven kernel autotuner: per-device tuned launch configs.

PR 7 gave every eager ``itemset_count`` launch a measured-vs-predicted
ledger per geometry bucket (``kernel_model.record_launch`` →
``obs.kernel_efficiency``).  This module CONSUMES it — the offline+online
loop the ROADMAP autotuning item asks for:

  * **offline sweep** (:func:`sweep`, driven by ``tools/autotune.py``):
    micro-benchmark the candidate lattice — ``block_k ∈ {64,128,256,512}``,
    ``accum ∈ {vpu_int32, mxu_f32}`` (the 2^24 weight-sum bound is
    respected: oversized geometries never get an MXU candidate), and a
    ``chunk_rows`` grid for the streaming sweep — over bucketized launch
    geometries, and persist the winner per (device-kind, geometry-bucket)
    in a versioned JSON :class:`TuningTable`.
  * **resolution seam** (:func:`resolve_launch_config`): every call site
    that used to hard-code ``block_k=256`` / ``accum="vpu_int32"`` /
    ``chunk_rows`` heuristics now passes ``None`` and lets this function
    look the geometry's bucket up in the active table — falling back to
    the original defaults when there is no table or no matching entry.
    Resolution happens EAGERLY (host-side, concrete shapes) so jit caches
    always see concrete static arguments.
  * **online staleness** (:func:`staleness_report`): the live per-bucket
    efficiency ledger is compared against the sweep-time efficiency of the
    recorded runner-up candidate; a tuned entry whose measured ratio
    drifts below that alternative (x ``STALE_MARGIN``) is flagged stale —
    the signal to re-run the sweep.

Config choice NEVER changes counts: every candidate is bit-exact (the PBT
battery in ``tests/test_autotune.py`` pins dense, streaming, and GFP paths
across the whole lattice), so a bad table can only cost speed.

Table discovery precedence: ``$REPRO_TUNE_TABLE`` (explicit path) → the
user cache (``~/.cache/repro/autotune/<device-kind>.json``, override root
with ``$REPRO_CACHE_DIR``) → the in-repo committed table for the CI box
(``roofline/tables/<device-kind>.json``).  ``$REPRO_AUTOTUNE=0`` disables
discovery entirely.  Schema-checked on load; anything invalid falls back
to the defaults (and bumps ``autotune_table_errors_total``).

CPU-interpret caveat: on this container the kernel runs in Pallas
interpret mode, so sweep timings measure the Python interpreter, not a
TPU — the committed CPU table keeps CI honest about the MECHANISM (tuned
must never lose to default; ``BENCH_tune.json`` gates it) while absolute
win margins only mean something on real hardware.
"""
from __future__ import annotations

import json
import math
import os
import re
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

from .. import obs
from .kernel_model import bucket_shape, geometry_bucket, predicted_seconds
from .peaks import local_peaks

__all__ = [
    "LaunchConfig", "TuningTable", "TableEntry", "TableError",
    "DEFAULT_BLOCK_K", "DEFAULT_BLOCK_N", "DEFAULT_ACCUM", "DEFAULT_CONFIG",
    "BLOCK_K_LATTICE", "ACCUM_LATTICE", "CHUNK_ROWS_GRID", "MXU_MAX_ROWS",
    "SCHEMA_VERSION", "STALE_MARGIN",
    "resolve_launch_config", "resolve_serve_block_k", "candidate_configs",
    "sweep", "save_table", "load_table", "table_to_dict", "table_from_dict",
    "set_active_table", "clear_active_table", "active_table",
    "describe_active", "device_kind", "repo_table_path", "cache_table_path",
    "default_table_paths", "staleness_report", "derived_chooser_thresholds",
]

# Today's hard-coded constants, now the documented fallback.
DEFAULT_BLOCK_K = 256
DEFAULT_BLOCK_N = 1024
DEFAULT_ACCUM = "vpu_int32"

# The candidate lattice the sweep measures.
BLOCK_K_LATTICE = (64, 128, 256, 512)
ACCUM_LATTICE = ("vpu_int32", "mxu_f32")
CHUNK_ROWS_GRID = (0, 4096, 16384)      # 0 = the staging-budget heuristic

# The sweep's synthetic problems carry unit weights, so a launch's per-class
# weight sum is its row count: mxu_f32 candidates stop at 2^24 rows (the
# ops.py weight-sum bound).
MXU_MAX_ROWS = 1 << 24

# The serve seam's reference micro-batch: the batcher pads each flush's K up
# to a block_k multiple, so the padded launch costs us(k=block_k) for any
# flush of <= block_k queries — an effect a fixed-K sweep cannot see.  The
# serve view times each candidate at its OWN padded geometry (k = block_k)
# and picks the cheapest flush for a batch of this size.
SERVE_REF_BATCH = 64

SCHEMA_VERSION = 1

# A non-default winner must beat the default by >3% to displace it — sweeps
# share a noisy box; a coin-flip "win" must not churn the table.
KEEP_DEFAULT_WITHIN = 0.97

# Staleness: flag when live efficiency < alternative's sweep efficiency x this.
STALE_MARGIN = 0.9

# The launch-overhead assumption (us) the hand-tuned chooser crossovers
# encode: DEFAULT_MIN_DEPTH=4 / DEFAULT_TINY_ROWS were picked for a dispatch
# cost of about this much.  Measured overhead scales the derived thresholds
# relative to it (docs/autotuning.md).
REF_LAUNCH_OVERHEAD_US = 100.0


@dataclass(frozen=True)
class LaunchConfig:
    """One launch configuration.  ``chunk_rows`` is None for the planner's
    staging-budget heuristic; ``source`` says where the config came from."""
    block_k: int = DEFAULT_BLOCK_K
    block_n: int = DEFAULT_BLOCK_N
    accum: str = DEFAULT_ACCUM
    chunk_rows: Optional[int] = None
    source: str = "default"


DEFAULT_CONFIG = LaunchConfig()


class TableError(ValueError):
    """A tuning table failed schema validation (load falls back to defaults)."""


@dataclass
class TableEntry:
    """Winner + evidence for one geometry bucket.  ``serve_block_k`` is the
    serve-seam winner (batcher padding view, timed at k = block_k per
    candidate); None means no serve view was swept — the serve path then
    keeps its default block."""
    config: LaunchConfig
    us: float                                  # winner, best-of-repeats
    efficiency: float                          # predicted_s / measured_s
    candidates: Dict[str, float] = field(default_factory=dict)
    chunk_candidates: Dict[str, float] = field(default_factory=dict)
    serve_block_k: Optional[int] = None
    serve_candidates: Dict[str, float] = field(default_factory=dict)


@dataclass
class TuningTable:
    device_kind: str
    entries: Dict[str, TableEntry]
    created: str = ""
    schema: int = SCHEMA_VERSION
    source: str = "<memory>"


# -- hot-path counters (bound once; registry resets keep them valid) ---------
_M_RESOLVE_DEFAULT = obs.REGISTRY.counter("autotune_resolutions_total",
                                          source="default")
_M_RESOLVE_TABLE = obs.REGISTRY.counter("autotune_resolutions_total",
                                        source="table")
_M_TABLE_ERRORS = obs.REGISTRY.counter("autotune_table_errors_total")

# last swallowed error per fallback site (device probe, serve-block probe):
# surfaced through the telemetry section so a chronically failing probe is
# visible in stats() instead of silently pinning the defaults
LAST_FALLBACKS: Dict[str, str] = {}


def _note_fallback(site: str, exc: BaseException) -> None:
    """Account one swallowed fallback: bounded-label counter + context."""
    LAST_FALLBACKS[site] = f"{type(exc).__name__}: {exc}"
    obs.REGISTRY.counter("autotune_fallbacks_total", site=site).inc()


# -- active-table state ------------------------------------------------------
# pinned: an explicit set_active_table() call (tests pin None = defaults).
# resolved: lazy discovery already ran (clear_active_table() re-arms it).
_STATE = {"pinned": False, "resolved": False, "table": None}


def device_kind() -> str:
    """Normalized device-kind token for table file names ('cpu',
    'tpu_v5_lite'…).  A device that cannot be asked raises: answering 'cpu'
    would load the CPU table's launch configs on an accelerator."""
    import jax
    kind = str(jax.devices()[0].device_kind)
    token = re.sub(r"[^a-z0-9_.-]+", "_", kind.lower()).strip("_")
    if not token:
        raise ValueError(f"device kind {kind!r} names no tuning table")
    return token


def repo_table_path(kind: Optional[str] = None) -> str:
    return os.path.join(os.path.dirname(__file__), "tables",
                        f"{kind or device_kind()}.json")


def cache_table_path(kind: Optional[str] = None) -> str:
    root = os.environ.get("REPRO_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return os.path.join(root, "repro", "autotune",
                        f"{kind or device_kind()}.json")


def default_table_paths() -> Tuple[str, ...]:
    """Discovery precedence: env override, user cache, committed repo table."""
    env = os.environ.get("REPRO_TUNE_TABLE")
    paths = [env] if env else []
    paths += [cache_table_path(), repo_table_path()]
    return tuple(paths)


def set_active_table(table: Optional[TuningTable]) -> None:
    """Pin the active table (None = pin to the defaults, discovery off)."""
    _STATE.update(pinned=True, resolved=True, table=table)


def clear_active_table() -> None:
    """Unpin and re-arm lazy discovery (the process-start state)."""
    _STATE.update(pinned=False, resolved=False, table=None)


def active_table() -> Optional[TuningTable]:
    """The table ``resolve_launch_config`` consults (lazy discovery)."""
    if not _STATE["resolved"]:
        _STATE["table"] = _discover_table()
        _STATE["resolved"] = True
    return _STATE["table"]


def _discover_table() -> Optional[TuningTable]:
    if os.environ.get("REPRO_AUTOTUNE", "1").lower() in ("0", "off", "false"):
        return None
    for path in default_table_paths():
        if not os.path.isfile(path):
            continue
        try:
            return load_table(path)
        except (TableError, OSError):
            _M_TABLE_ERRORS.inc()
    return None


def describe_active() -> str:
    """One-line banner text for the launchers: which table (if any) is live."""
    t = active_table()
    if t is None:
        return "default launch configs (no tuning table)"
    return (f"tuning table [{t.device_kind}] {len(t.entries)} entries "
            f"from {t.source}")


# -- the seam ----------------------------------------------------------------

def resolve_launch_config(n: int, k: int, w: int, c: int) -> LaunchConfig:
    """Launch config for one (N, K, W, C) geometry: the active table's entry
    for its bucket, or :data:`DEFAULT_CONFIG`.

    An ``mxu_f32`` pick is re-checked against the launch's actual weights
    in ``kernels/itemset_count/ops.py``: a launch whose per-class weight
    sum reaches 2^24 runs on the exact VPU accumulator instead."""
    t = active_table()
    if t is None:
        _M_RESOLVE_DEFAULT.inc()
        return DEFAULT_CONFIG
    entry = t.entries.get(geometry_bucket(n, k, w, c))
    if entry is None:
        _M_RESOLVE_DEFAULT.inc()
        return DEFAULT_CONFIG
    _M_RESOLVE_TABLE.inc()
    return entry.config


def resolve_serve_block_k(store) -> int:
    """Serve-path block_k for a count store (CountServer/MicroBatcher init).

    Serve launches pad K up to block_k multiples, so the nominal K for the
    bucket lookup is the default block itself; N/W/C come from the store's
    resident geometry.  Only the entry's ``serve_block_k`` (the padding-
    aware serve view) is honored — the fixed-K winner optimizes a different
    objective and must not shrink or grow the batcher's padding untested.
    Anything unmeasurable falls back to the default."""
    try:
        n = int(getattr(store, "base_rows", 0) or getattr(store, "n_rows", 0))
        w = int(store.vocab.n_words)
        c = int(store.n_classes)
    except Exception as e:
        _note_fallback("serve_block_k", e)
        return DEFAULT_BLOCK_K
    t = active_table()
    if t is None:
        return DEFAULT_BLOCK_K
    entry = t.entries.get(geometry_bucket(max(n, 1), DEFAULT_BLOCK_K,
                                          max(w, 1), max(c, 1)))
    if entry is None or not entry.serve_block_k:
        return DEFAULT_BLOCK_K
    return int(entry.serve_block_k)


# -- persistence -------------------------------------------------------------

def table_to_dict(table: TuningTable) -> dict:
    return {
        "schema": table.schema,
        "device_kind": table.device_kind,
        "created": table.created,
        "entries": {
            bucket: {
                "block_k": e.config.block_k,
                "block_n": e.config.block_n,
                "accum": e.config.accum,
                "chunk_rows": int(e.config.chunk_rows or 0),
                "us": e.us,
                "efficiency": e.efficiency,
                "candidates": e.candidates,
                "chunk_candidates": e.chunk_candidates,
                "serve_block_k": int(e.serve_block_k or 0),
                "serve_candidates": e.serve_candidates,
            }
            for bucket, e in table.entries.items()
        },
    }


def table_from_dict(doc: dict, source: str = "<memory>") -> TuningTable:
    """Schema-checked deserialization; raises :class:`TableError` on any
    violation (the loaders then fall back to the defaults)."""
    if not isinstance(doc, dict):
        raise TableError("tuning table must be a JSON object")
    if doc.get("schema") != SCHEMA_VERSION:
        raise TableError(f"unsupported tuning-table schema "
                         f"{doc.get('schema')!r} (want {SCHEMA_VERSION})")
    kind = doc.get("device_kind")
    if not isinstance(kind, str) or not kind:
        raise TableError("device_kind must be a non-empty string")
    raw = doc.get("entries")
    if not isinstance(raw, dict):
        raise TableError("entries must be an object")
    entries: Dict[str, TableEntry] = {}
    for bucket, e in raw.items():
        try:
            bucket_shape(bucket)
        except ValueError as exc:
            raise TableError(str(exc)) from exc
        if not isinstance(e, dict):
            raise TableError(f"{bucket}: entry must be an object")
        bk, bn = e.get("block_k"), e.get("block_n", DEFAULT_BLOCK_N)
        accum = e.get("accum")
        cr = e.get("chunk_rows", 0)
        us = e.get("us")
        if bk not in BLOCK_K_LATTICE:
            raise TableError(f"{bucket}: block_k {bk!r} outside the lattice "
                             f"{BLOCK_K_LATTICE}")
        if not isinstance(bn, int) or bn <= 0 or bn % 128:
            raise TableError(f"{bucket}: block_n must be a positive "
                             "multiple of 128 (the lane width)")
        if accum not in ACCUM_LATTICE:
            raise TableError(f"{bucket}: accum {accum!r} outside "
                             f"{ACCUM_LATTICE}")
        if not isinstance(cr, int) or cr < 0:
            raise TableError(f"{bucket}: chunk_rows must be an int >= 0")
        if not isinstance(us, (int, float)) or us <= 0:
            raise TableError(f"{bucket}: us must be a positive number")
        sbk = e.get("serve_block_k", 0)
        if sbk not in (0, None) and sbk not in BLOCK_K_LATTICE:
            raise TableError(f"{bucket}: serve_block_k {sbk!r} outside the "
                             f"lattice {BLOCK_K_LATTICE}")
        entries[bucket] = TableEntry(
            config=LaunchConfig(block_k=bk, block_n=bn, accum=accum,
                                chunk_rows=cr or None, source="table"),
            us=float(us),
            efficiency=float(e.get("efficiency", 0.0)),
            candidates={str(kk): float(v)
                        for kk, v in (e.get("candidates") or {}).items()},
            chunk_candidates={str(kk): float(v)
                              for kk, v in
                              (e.get("chunk_candidates") or {}).items()},
            serve_block_k=sbk or None,
            serve_candidates={str(kk): float(v)
                              for kk, v in
                              (e.get("serve_candidates") or {}).items()},
        )
    return TuningTable(device_kind=kind, entries=entries,
                       created=str(doc.get("created", "")),
                       schema=SCHEMA_VERSION, source=source)


def save_table(table: TuningTable, path: str) -> str:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(table_to_dict(table), f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_table(path: str) -> TuningTable:
    try:
        with open(path) as f:
            doc = json.load(f)
    except json.JSONDecodeError as exc:
        raise TableError(f"{path}: not valid JSON ({exc})") from exc
    return table_from_dict(doc, source=path)


# -- the offline sweep -------------------------------------------------------

def candidate_configs(n: int) -> Tuple[Tuple[int, str], ...]:
    """(block_k, accum) lattice for a bucket, MXU guard applied."""
    return tuple((bk, acc) for bk in BLOCK_K_LATTICE for acc in ACCUM_LATTICE
                 if not (acc == "mxu_f32" and n >= MXU_MAX_ROWS))


def _cand_key(block_k: int, accum: str) -> str:
    return f"bk{block_k}/{accum}"


def _time_best_of(fn: Callable[[], object], repeats: int) -> float:
    """Best-of-N wall time in microseconds (first call warms the jit cache)."""
    fn()
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _synthetic_problem(n: int, k: int, w: int, c: int):
    """Deterministic synthetic workload for one bucket: random bitmap rows,
    targets derived from row pairs (plausible containment density), unit
    weights."""
    import numpy as np

    rng = np.random.default_rng([0x7A11, n, k, w, c])
    tx = rng.integers(0, 1 << 32, size=(n, w), dtype=np.uint64) \
        .astype(np.uint32)
    picks = rng.integers(0, n, size=(2, k))
    tgt = (tx[picks[0]] & tx[picks[1]]).astype(np.uint32)
    wts = np.ones((n, c), np.int32)
    return tx, tgt, wts


def sweep(geometries: Iterable[Tuple[int, int, int, int]], *,
          repeats: int = 3,
          block_ks: Sequence[int] = BLOCK_K_LATTICE,
          accums: Sequence[str] = ACCUM_LATTICE,
          chunk_grid: Sequence[int] = CHUNK_ROWS_GRID,
          kind: Optional[str] = None,
          created: str = "",
          log: Optional[Callable[[str], None]] = None) -> TuningTable:
    """Micro-benchmark the candidate lattice over each geometry's BUCKET and
    return the winning :class:`TuningTable` (not yet active or persisted).

    Metrics and kernel wall-time telemetry are suspended for the duration:
    losing candidates must not pollute the live launch counts and
    efficiency ledger the staleness rule reads."""
    import jax.numpy as jnp
    import numpy as np

    from ..kernels.itemset_count import itemset_counts
    from ..mining.plan import choose_chunk_rows
    from ..mining.stream import streaming_counts

    buckets = []
    for g in geometries:
        b = geometry_bucket(*g)
        if b not in buckets:
            buckets.append(b)

    entries: Dict[str, TableEntry] = {}
    # sweep-time efficiency needs this chip's peaks; an unknown kind (the
    # CPU among them) records 0.0 — no ratio against another chip's peak
    peaks = local_peaks()
    prev_metrics, prev_timing = obs.REGISTRY.enabled, obs.KERNEL_TIMING
    obs.configure(metrics=False, kernel_timing=False)
    try:
        for bucket in buckets:
            n, k, w, c = bucket_shape(bucket)
            tx, tgt, wts = _synthetic_problem(n, k, w, c)
            txd, tgtd, wtsd = jnp.asarray(tx), jnp.asarray(tgt), \
                jnp.asarray(wts)

            cands: Dict[str, float] = {}
            for bk in block_ks:
                for acc in accums:
                    if acc == "mxu_f32" and n >= MXU_MAX_ROWS:
                        continue
                    cands[_cand_key(bk, acc)] = _time_best_of(
                        lambda bk=bk, acc=acc: np.asarray(itemset_counts(
                            txd, tgtd, wtsd, block_k=bk,
                            block_n=DEFAULT_BLOCK_N, accum=acc)),
                        repeats)
            default_key = _cand_key(DEFAULT_BLOCK_K, DEFAULT_ACCUM)
            best_key = min(cands, key=cands.get)  # type: ignore[arg-type]
            if (default_key in cands and best_key != default_key
                    and cands[best_key]
                    > cands[default_key] * KEEP_DEFAULT_WITHIN):
                best_key = default_key            # not a decisive win
            win_bk, win_acc = best_key.split("/")
            win_bk = int(win_bk[2:])

            # chunk_rows grid with the winning block config (0 = heuristic)
            chunk_cands: Dict[str, float] = {}
            heuristic = choose_chunk_rows(w, c)
            if n > 1024:
                for cr in chunk_grid:
                    eff = int(cr) or heuristic
                    if cr and (eff >= n and heuristic >= n):
                        continue    # indistinguishable from the heuristic
                    chunk_cands[str(int(cr))] = _time_best_of(
                        lambda eff=eff: np.asarray(streaming_counts(
                            tx, tgt, wts, chunk_rows=eff, block_k=win_bk,
                            block_n=DEFAULT_BLOCK_N, accum=win_acc)),
                        max(1, repeats - 1))
            win_cr = 0
            if chunk_cands:
                best_cr = min(chunk_cands, key=chunk_cands.get)  # type: ignore[arg-type]
                if ("0" in chunk_cands and best_cr != "0"
                        and chunk_cands[best_cr]
                        > chunk_cands["0"] * KEEP_DEFAULT_WITHIN):
                    best_cr = "0"
                win_cr = int(best_cr)

            # serve view: the batcher pads a flush's K up to block_k, so a
            # <= block_k-query flush costs a k=block_k launch — time each
            # candidate at its OWN padded geometry.  Structural (smaller
            # block = strictly less padded work), unlike the fixed-K tie.
            serve_cands: Dict[str, float] = {}
            serve_bk = 0
            if k > min(block_ks):
                for bk in block_ks:
                    stx, stgt, swts = _synthetic_problem(n, int(bk), w, c)
                    stxd, stgtd, swtsd = (jnp.asarray(stx), jnp.asarray(stgt),
                                          jnp.asarray(swts))
                    flushes = max(1, -(-SERVE_REF_BATCH // int(bk)))
                    serve_cands[str(int(bk))] = flushes * _time_best_of(
                        lambda: np.asarray(itemset_counts(
                            stxd, stgtd, swtsd, block_k=int(bk),
                            block_n=DEFAULT_BLOCK_N, accum=win_acc)),
                        max(1, repeats - 1))
                best_sbk = min(serve_cands, key=serve_cands.get)  # type: ignore[arg-type]
                default_sbk = str(DEFAULT_BLOCK_K)
                if (default_sbk in serve_cands and best_sbk != default_sbk
                        and serve_cands[best_sbk]
                        > serve_cands[default_sbk] * KEEP_DEFAULT_WITHIN):
                    best_sbk = default_sbk
                serve_bk = int(best_sbk)

            us = cands[best_key]
            entries[bucket] = TableEntry(
                config=LaunchConfig(block_k=win_bk, block_n=DEFAULT_BLOCK_N,
                                    accum=win_acc, chunk_rows=win_cr or None,
                                    source="table"),
                us=us,
                efficiency=(predicted_seconds(n, k, w, c, peaks)
                            / (us * 1e-6) if peaks is not None else 0.0),
                candidates=cands,
                chunk_candidates=chunk_cands,
                serve_block_k=serve_bk or None,
                serve_candidates=serve_cands,
            )
            if log is not None:
                log(f"autotune: {bucket}: {best_key} "
                    f"({us:.0f}us, chunk_rows={win_cr or 'auto'}, "
                    f"serve_block_k={serve_bk or 'default'}, "
                    f"{len(cands)} candidates)")
    finally:
        obs.configure(metrics=prev_metrics, kernel_timing=prev_timing)
    return TuningTable(device_kind=kind or device_kind(), entries=entries,
                       created=created)


# -- the online feedback loop ------------------------------------------------

def staleness_report(table: Optional[TuningTable] = None,
                     snap: Optional[dict] = None) -> Dict[str, dict]:
    """Per-bucket staleness verdicts from the live efficiency ledger.

    An entry is STALE when its live measured-vs-predicted efficiency has
    drifted below the sweep-time efficiency of the recorded runner-up
    candidate (x :data:`STALE_MARGIN`): the config that won the sweep is now
    delivering less than the alternative did back then, so the sweep should
    be re-run.  Buckets with no live launches report ``stale: False`` with
    a reason."""
    t = table if table is not None else active_table()
    if t is None:
        return {}
    live = obs.kernel_efficiency(snap)
    out: Dict[str, dict] = {}
    for bucket, entry in t.entries.items():
        win_key = _cand_key(entry.config.block_k, entry.config.accum)
        alts = {kk: us for kk, us in entry.candidates.items()
                if kk != win_key and us > 0}
        row = {"stale": False, "config": win_key,
               "sweep_efficiency": entry.efficiency,
               "live_efficiency": None, "launches": 0,
               "alternative": None, "alternative_efficiency": None}
        if alts:
            alt_key = min(alts, key=alts.get)  # type: ignore[arg-type]
            row["alternative"] = alt_key
            # sweep-time efficiency of the runner-up, from its measured us
            row["alternative_efficiency"] = (entry.efficiency * entry.us
                                             / alts[alt_key])
        ledger = live.get(bucket)
        if ledger and ledger.get("efficiency") is not None:
            row["live_efficiency"] = ledger["efficiency"]
            row["launches"] = ledger["launches"]
            if row["alternative_efficiency"] is not None:
                row["stale"] = bool(
                    ledger["efficiency"]
                    < row["alternative_efficiency"] * STALE_MARGIN)
        else:
            row["reason"] = "no live launches recorded for this bucket"
        out[bucket] = row
    return out


def _telemetry_section() -> dict:
    """The ``stats()["telemetry"]["autotune"]`` block (registered below)."""
    t = active_table()
    if t is None:
        return {"active": False, "source": "default", "entries": {},
                "stale": {}, "fallbacks": dict(LAST_FALLBACKS)}
    return {
        "active": True,
        "source": t.source,
        "fallbacks": dict(LAST_FALLBACKS),
        "device_kind": t.device_kind,
        "entries": {
            bucket: {"block_k": e.config.block_k, "block_n": e.config.block_n,
                     "accum": e.config.accum,
                     "chunk_rows": e.config.chunk_rows,
                     "serve_block_k": e.serve_block_k, "us": e.us}
            for bucket, e in t.entries.items()
        },
        "stale": staleness_report(t),
    }


obs.register_section("autotune", _telemetry_section)


# -- measured chooser crossovers ---------------------------------------------

def _launch_cost_fit(table: TuningTable) -> Optional[Tuple[float, float]]:
    """Least-squares fit ``us ≈ overhead + per_row * n`` over the table's
    winner timings (needs >= 2 distinct row buckets).  Returns
    ``(overhead_us, per_row_us)`` with sane floors, or None."""
    pts = []
    for bucket, e in table.entries.items():
        try:
            n, _, _, _ = bucket_shape(bucket)
        except ValueError:
            continue
        pts.append((float(n), e.us))
    if len({p[0] for p in pts}) < 2:
        return None
    mx = sum(p[0] for p in pts) / len(pts)
    my = sum(p[1] for p in pts) / len(pts)
    var = sum((p[0] - mx) ** 2 for p in pts)
    cov = sum((p[0] - mx) * (p[1] - my) for p in pts)
    per_row = max(cov / var, 1e-6) if var > 0 else 1e-6
    overhead = max(my - per_row * mx, 1.0)
    return overhead, per_row


def _stream_ratio(table: TuningTable) -> Optional[float]:
    """Median measured single-pass/chunked throughput ratio (<= ~1 when
    chunking costs something; None without chunk evidence)."""
    ratios = []
    for e in table.entries.values():
        chunked = [us for cr, us in e.chunk_candidates.items()
                   if cr != "0" and us > 0]
        if chunked and e.us > 0:
            ratios.append(e.us / min(chunked))
    if not ratios:
        return None
    ratios.sort()
    return ratios[len(ratios) // 2]


def derived_chooser_thresholds(
        table: Optional[TuningTable] = None) -> Dict[str, int]:
    """Chooser crossovers derived from the table's MEASURED throughput
    (empty dict without a table or enough evidence → the chooser keeps its
    hand-tuned constants).  All values are clamped to sane ranges: sweep
    timings on the CPU-interpret container are wild, and a mistuned
    threshold must only ever cost speed, never sanity.

      * ``tiny_rows``      — rows where launch overhead ≈ sweep cost
                             (``overhead / per_row``): below it, dense
                             always wins.
      * ``min_depth``      — gfp crossover shifted by how much pricier a
                             launch is than the :data:`REF_LAUNCH_OVERHEAD_US`
                             assumption behind the default depth 4
                             (``4 - log2(overhead/ref)``): pricier launches
                             → guided counting pays off shallower.
      * ``stream_threshold_bytes`` — dense-vs-streaming residency crossover
                             scaled inversely with the measured chunking
                             penalty: near-free chunking lowers the
                             threshold (stream earlier, buy headroom),
                             expensive chunking raises it (cling to
                             residency).
      * ``gfp_host_rows``  — the GFP hybrid's host-vs-kernel block
                             crossover, same overhead/per-row quantity as
                             ``tiny_rows`` on its own clamp.
    """
    t = table if table is not None else active_table()
    if t is None:
        return {}
    out: Dict[str, int] = {}
    fit = _launch_cost_fit(t)
    if fit is not None:
        overhead_us, per_row_us = fit
        crossover = int(round(overhead_us / per_row_us))
        out["tiny_rows"] = min(65536, max(512, crossover))
        # the sweep measures only the KERNEL side of the hybrid, so measured
        # evidence can raise the host crossover (launches proved expensive)
        # but never push blocks onto the kernel below the hand-tuned default
        # (4096 = gfp_backend.DEFAULT_HOST_BLOCK_ROWS; no host cost was swept
        # to justify that direction)
        out["gfp_host_rows"] = min(16384, max(4096, crossover))
        shift = math.log2(max(overhead_us, 1.0) / REF_LAUNCH_OVERHEAD_US)
        out["min_depth"] = min(8, max(2, round(4 - shift)))
    rho = _stream_ratio(t)
    if rho is not None:
        from ..mining.stream import device_stream_threshold_bytes
        base = device_stream_threshold_bytes()
        scaled = int(base / (2 * max(rho, 0.25)))
        out["stream_threshold_bytes"] = min(2 * base, max(base // 2, scaled))
    return out
