"""Distributed multitude-targeted mining — the GFP-growth engine on a mesh.

Parallel decomposition (maps the paper's workload to a (data, model) mesh):

  * transactions (N axis)  -> sharded over the 'data' mesh axis (and 'pod'):
    each device counts its local rows; ONE psum of the small (K_loc, C) count
    block per launch is the only communication — the dense analogue of
    "collecting counts from reduced conditional trees" with no tree traffic;
  * targets (K axis)       -> sharded over the 'model' mesh axis: devices hold
    disjoint target blocks, so the count matrix never materializes globally
    (multitude-targeted = K can be millions).

Scaling: work O(N·K·W / P) per device, comm O(K·C / model_size) per level —
independent of N.  At 1000+ nodes the N axis shards freely (transactions are
i.i.d. rows); elasticity = re-encode shard boundaries, nothing else changes.

Fault tolerance: level-synchronous mining checkpoints (level index + frequent
frontier + accumulated counts) via MiningCheckpoint — a restart (possibly on a
DIFFERENT mesh shape) resumes from the last completed level.
"""
from __future__ import annotations

import functools
import json
import os
import tempfile
from dataclasses import dataclass
from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.itemset_count import itemset_counts
from ..kernels.itemset_count.ops import checked_accum, weight_sum_bound
from .encode import ItemVocab, encode_targets

Item = Hashable


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.lru_cache(maxsize=None)
def _count_shard_fn(mesh: Mesh, data_axes: Tuple[str, ...],
                    model_axis: Optional[str], use_kernel: bool,
                    block_k: Optional[int] = None,
                    block_n: Optional[int] = None,
                    accum: Optional[str] = None):
    """Build (and cache) the jitted shard_map counting launch.

    Cached on (mesh, axes, use_kernel, launch config) so repeated launches —
    per mining level, and per chunk of a streaming sweep — reuse one
    executable per input shape instead of re-tracing a fresh closure every
    call.  The launch config is part of the cache key ON PURPOSE: callers
    resolve the tuning table eagerly and pass CONCRETE values, so a table
    swap retraces instead of silently reusing a stale config baked into a
    cached trace.
    """
    tx_spec = P(data_axes, None)
    tgt_spec = P(model_axis, None)
    w_spec = P(data_axes, None)
    out_spec = P(model_axis, None)

    @functools.partial(
        jax.jit,
        in_shardings=(NamedSharding(mesh, tx_spec), NamedSharding(mesh, tgt_spec),
                      NamedSharding(mesh, w_spec)),
        out_shardings=NamedSharding(mesh, out_spec),
    )
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(tx_spec, tgt_spec, w_spec), out_specs=out_spec,
        check_vma=False,  # pallas_call out_shape carries no vma annotation
    )
    def count_shard(tx, tgt, wts):
        local = itemset_counts(tx, tgt, wts, use_kernel=use_kernel,
                               block_k=block_k, block_n=block_n, accum=accum)
        return jax.lax.psum(local, data_axes)

    return count_shard


def _resolve_shard_config(n_local: int, k_local: int, w: int, c: int,
                          weights, weight_bound: Optional[int] = None):
    """Per-DEVICE launch config for a sharded launch: the table is keyed on
    the geometry each device actually sees (its local row/target block), not
    the global problem.  The mxu_f32 weight-sum bound is checked here, on
    ``weight_bound`` or else the whole ``weights`` (which bound every
    device's share): inside the shard_map trace they are abstract."""
    from ..roofline import autotune
    cfg = autotune.resolve_launch_config(max(1, n_local), max(1, k_local),
                                         max(1, w), max(1, c))
    return cfg.block_k, cfg.block_n, checked_accum(None, cfg.accum, weights,
                                                   weight_bound)


def distributed_counts(
    tx_bits: np.ndarray,      # (N, W) uint32 (host; will be sharded)
    tgt_bits: np.ndarray,     # (K, W) uint32
    weights: np.ndarray,      # (N, C) int32
    mesh: Mesh,
    *,
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: Optional[str] = "model",
    use_kernel: bool = True,
    chunk_rows: Optional[int] = None,
    start_chunk: int = 0,
    init: Optional[np.ndarray] = None,
    on_chunk=None,
) -> np.ndarray:              # (K, C) int32
    """Exact counts on a mesh: N over data axes, K over the model axis.

    ``chunk_rows`` composes sharding-over-devices with streaming-within-
    device: the N axis is swept in host-side chunks (each chunk itself
    sharded over the data axes), so per-device residency is
    O(chunk_rows / data_size) regardless of total N.  Counts are int32 sums —
    the chunked sweep is bit-identical to the single pass.

    ``start_chunk`` / ``init`` / ``on_chunk`` follow the streaming resume
    discipline (``mining/stream.py``): ``on_chunk(j, acc)`` fires after
    chunk ``j`` with the running int32 accumulator, and a resumed sweep
    seeded with a checkpointed accumulator skips the chunks already counted
    — the driver's mid-level checkpoint hook, now available on a mesh.
    """
    k, w = tgt_bits.shape
    n, c = weights.shape
    # counts are bounded by the per-class weight-column sums; guard BEFORE any
    # device work — the kernel and psum run in int32 and would wrap silently
    bound = weight_sum_bound(weights)
    if bound > np.iinfo(np.int32).max:
        raise OverflowError("per-class weight totals exceed int32; counts "
                            "could wrap — split the DB")
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    msize = mesh.shape[model_axis] if model_axis else 1
    k_pad = _round_up(max(k, 1), msize)
    tgt_p = np.zeros((k_pad, w), np.uint32)
    tgt_p[:k] = tgt_bits

    if chunk_rows is not None and 0 < chunk_rows < n:
        from .plan import stream_chunks
        # fixed chunk shape (zero-pad the ragged tail) and a single device
        # copy of the target block: one executable, one target upload
        n_pad = _round_up(chunk_rows, dsize)
        count_shard = _count_shard_fn(
            mesh, tuple(data_axes), model_axis, use_kernel,
            *_resolve_shard_config(n_pad // dsize, k_pad // msize, w, c,
                                   weights, bound))
        tgt_d = jnp.asarray(tgt_p)
        txc = np.zeros((n_pad, tx_bits.shape[1]), np.uint32)
        wc = np.zeros((n_pad, c), np.int32)
        total = (np.zeros((k, c), np.int64) if init is None
                 else np.asarray(init).astype(np.int64))
        chunks = stream_chunks(n, chunk_rows)
        if start_chunk >= len(chunks):
            return total.astype(np.int32)  # fully counted: resume is a no-op
        for j in range(start_chunk, len(chunks)):
            s, e = chunks[j]
            txc[: e - s] = tx_bits[s:e]
            txc[e - s:] = 0
            wc[: e - s] = weights[s:e]
            wc[e - s:] = 0
            # host int64 accumulation of the small (K, C) block (per-chunk
            # sync; the block is tiny).  The upfront weight-sum guard bounds
            # every count under int32, so the final cast cannot wrap.
            total += np.asarray(count_shard(jnp.asarray(txc), tgt_d,
                                            jnp.asarray(wc)))[:k]
            if on_chunk is not None:
                on_chunk(j, total.astype(np.int32))
        return total.astype(np.int32)

    base = (np.zeros((k, c), np.int32) if init is None
            else np.array(np.asarray(init), np.int32))
    if start_chunk >= 1:
        return base                        # single-chunk resume discipline
    n_pad = _round_up(max(n, 1), dsize)
    count_shard = _count_shard_fn(
        mesh, tuple(data_axes), model_axis, use_kernel,
        *_resolve_shard_config(n_pad // dsize, k_pad // msize, w, c,
                               weights, bound))
    tx_p = np.zeros((n_pad, tx_bits.shape[1]), np.uint32)
    tx_p[:n] = tx_bits
    w_p = np.zeros((n_pad, c), np.int32)
    w_p[:n] = weights
    out = base + np.asarray(count_shard(jnp.asarray(tx_p), jnp.asarray(tgt_p),
                                        jnp.asarray(w_p)))[:k]
    if on_chunk is not None:
        on_chunk(0, out)
    return out


def place_rows(
    bits: np.ndarray,        # (N, W) uint32, host
    weights: np.ndarray,     # (N, C) int32, host
    mesh: Mesh,
    *,
    data_axes: Tuple[str, ...] = ("data",),
):
    """Row-shard an encoded DB over the mesh data axes ONCE, for reuse.

    Pads N to the data-axis multiple (zero rows count nothing) and
    ``device_put``s both arrays with the row-partitioned sharding that
    :func:`resident_distributed_counts` expects.  The serving hot path calls
    this once per store version and then answers every query against the
    resident placement — no per-query H2D sweep upload."""
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    n = int(bits.shape[0])
    n_pad = _round_up(max(n, 1), dsize)
    bp = np.zeros((n_pad, bits.shape[1]), np.uint32)
    bp[:n] = bits
    wp = np.zeros((n_pad, weights.shape[1]), np.int32)
    wp[:n] = weights
    sharding = NamedSharding(mesh, P(data_axes, None))
    return (jax.device_put(bp, sharding), jax.device_put(wp, sharding))


def resident_distributed_counts(
    tx_dev,                   # (N_pad, W) uint32, placed by place_rows
    tgt_bits: np.ndarray,     # (K, W) uint32, host
    w_dev,                    # (N_pad, C) int32, placed by place_rows
    mesh: Mesh,
    *,
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: Optional[str] = None,
    use_kernel: bool = True,
    weight_bound: Optional[int] = None,
) -> np.ndarray:              # (K, C) int32
    """:func:`distributed_counts` for a RESIDENT row placement: every device
    counts its local rows, one psum all-reduces the small (K, C) block.

    The transaction rows and weights stay on the mesh across calls (the
    serving analogue of the resident ``DenseDB``); only the target block is
    padded and uploaded per call.  The int32 overflow guard is the CALLER's
    contract — a serving store guards its per-class row totals on every
    append, before rows ever reach the placement.  Its totals are also the
    ``weight_bound`` that spares the mxu_f32 check a gather of ``w_dev``."""
    k, w = tgt_bits.shape
    c = int(w_dev.shape[1])
    if k == 0:
        return np.zeros((0, c), np.int32)
    msize = mesh.shape[model_axis] if model_axis else 1
    k_pad = _round_up(k, msize)
    tgt_p = np.zeros((k_pad, w), np.uint32)
    tgt_p[:k] = tgt_bits
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    count_shard = _count_shard_fn(
        mesh, tuple(data_axes), model_axis, use_kernel,
        *_resolve_shard_config(int(tx_dev.shape[0]) // dsize,
                               k_pad // msize, w, c, w_dev, weight_bound))
    out = np.asarray(count_shard(tx_dev, jnp.asarray(tgt_p), w_dev))
    return np.array(out[:k], np.int32)


@dataclass
class MiningCheckpoint:
    """Restartable state of a level-synchronous mine.

    ``level``/``frequent`` record the last COMPLETED level; the optional
    ``partial`` dict records an in-flight level of a streaming sweep
    ({level, itemsets, next_chunk, acc}) so a restart resumes mid-level from
    the last completed chunk (see ``mining/stream.py``).
    """
    path: str

    def save(self, level: int, frequent: Dict[Tuple[Item, ...], int],
             meta: Optional[dict] = None,
             partial: Optional[dict] = None) -> None:
        tmp = self.path + ".tmp"
        payload = {
            "level": level,
            "frequent": [[list(k), int(v)] for k, v in frequent.items()],
            "meta": meta or {},
            "partial": partial,
        }
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, self.path)  # atomic

    def load(self) -> Optional[Tuple[int, Dict[Tuple[Item, ...], int], dict]]:
        state = self.load_state()
        if state is None:
            return None
        return state["level"], state["frequent"], state["meta"]

    def load_state(self) -> Optional[dict]:
        """Full state incl. the mid-level ``partial`` record (or None).
        A missing or EMPTY file means no state: saves are atomic (write tmp
        + rename), so a 0-byte file can only be a pre-created placeholder
        (e.g. ``mkstemp``), never a torn write."""
        if not os.path.exists(self.path) or os.path.getsize(self.path) == 0:
            return None
        with open(self.path) as f:
            payload = json.load(f)
        freq = {tuple(k): v for k, v in payload["frequent"]}
        return {
            "level": payload["level"],
            "frequent": freq,
            "meta": payload.get("meta", {}),
            "partial": payload.get("partial"),
        }


class DistributedMiner:
    """Level-synchronous exact frequent-itemset mining over a mesh, with
    optional per-level checkpointing (fault tolerance) and elastic resume.

    ``chunk_rows`` enables the streaming composition: every counting launch
    sweeps the N axis in host chunks, each chunk sharded over the data axes
    (sharding-over-devices x streaming-within-device)."""

    def __init__(self, mesh: Mesh, *, data_axes: Tuple[str, ...] = ("data",),
                 model_axis: Optional[str] = "model", use_kernel: bool = True,
                 checkpoint: Optional[MiningCheckpoint] = None,
                 chunk_rows: Optional[int] = None):
        self.mesh = mesh
        self.data_axes = data_axes
        self.model_axis = model_axis
        self.use_kernel = use_kernel
        self.checkpoint = checkpoint
        self.chunk_rows = chunk_rows

    def counts(self, tx_bits, tgt_bits, weights) -> np.ndarray:
        return distributed_counts(
            tx_bits, tgt_bits, weights, self.mesh,
            data_axes=self.data_axes, model_axis=self.model_axis,
            use_kernel=self.use_kernel, chunk_rows=self.chunk_rows)

    def gfp_counts(
        self,
        tis,                       # repro.core.TISTree
        tx_bits: np.ndarray,
        weights: np.ndarray,
        vocab: ItemVocab,
    ) -> Dict[Tuple[Item, ...], np.ndarray]:
        """The GFP-growth contract, distributed: counts for all TIS targets."""
        targets, keys, zeros = [], [], []
        for node in tis.targets():
            itemset = node.itemset()
            key = tuple(sorted(itemset, key=repr))
            if all(a in vocab for a in itemset):
                targets.append(itemset)
                keys.append(key)
            else:
                zeros.append(key)
        out = {k: np.zeros(weights.shape[1], np.int32) for k in zeros}
        if targets:
            masks = encode_targets(targets, vocab)
            rows = self.counts(tx_bits, masks, weights)
            for key, row in zip(keys, rows):
                out[key] = row
        return out

    def backend(self, tx_bits: np.ndarray, weights: np.ndarray,
                vocab: ItemVocab):
        """The miner's :class:`~repro.mining.backend.DistributedBackend` over
        host arrays.  With ``chunk_rows`` active the backend exposes the
        N-axis sweep's chunk grid to the driver (one resumable chunk per
        host chunk), so a mesh mine checkpoints MID-level — the sharding
        composition's last gap."""
        from .backend import DistributedBackend
        from .plan import stream_chunks

        n = int(tx_bits.shape[0])
        nbytes = int(tx_bits.nbytes + weights.nbytes)
        if self.chunk_rows is not None and 0 < self.chunk_rows < n:
            return DistributedBackend(
                lambda masks, **kw: distributed_counts(
                    tx_bits, masks, weights, self.mesh,
                    data_axes=self.data_axes, model_axis=self.model_axis,
                    use_kernel=self.use_kernel, chunk_rows=self.chunk_rows,
                    **kw),
                vocab, n, int(weights.shape[1]), nbytes=nbytes,
                n_chunks=len(stream_chunks(n, self.chunk_rows)),
                chunk_rows=self.chunk_rows)
        return DistributedBackend(
            lambda masks: self.counts(tx_bits, masks, weights),
            vocab, n, int(weights.shape[1]), nbytes=nbytes)

    def mine_frequent(
        self,
        tx_bits: np.ndarray,
        weights: np.ndarray,
        vocab: ItemVocab,
        min_count: float,
        *,
        class_column: Optional[int] = None,
        max_len: int = 0,
        on_chunk=None,
    ) -> Dict[Tuple[Item, ...], int]:
        """Shim over the unified driver (``mining/driver.py``): one mesh
        counting launch per level (singles included), per-level checkpoint
        saves — plus the driver's mid-level partial at N-chunk granularity
        when ``chunk_rows`` is active, so a restart (possibly on a DIFFERENT
        mesh shape: the signature is mesh-independent) skips any counted
        level AND any counted chunk of the in-flight level."""
        from .driver import mine_frequent as _driver_mine

        backend = self.backend(tx_bits, weights, vocab)
        return _driver_mine(backend, min_count, class_column=class_column,
                            max_len=max_len, checkpoint=self.checkpoint,
                            on_chunk=on_chunk)
