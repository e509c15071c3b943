"""Streaming out-of-core counting engine — N unbounded by device memory.

The dense engine (``dense.py``) requires the whole encoded bitmap resident in
one device allocation.  This module removes that limit the way "Mining
Frequent Itemsets from Secondary Memory" (Grahne & Zhu, 2004) does for
host-memory FP-trees, adapted to the TPU layout:

  * ``StreamingDB`` keeps the (U, W) bitmap + (U, C) class weights HOST-side
    and serves them in N-chunks;
  * ``streaming_counts`` sweeps the chunks through the SAME Pallas kernel,
    accumulating the small (K, C) count block on device
    (``itemset_counts_into``, donated accumulator).  Counts are int32 sums,
    so the sweep is bit-identical to a single dense pass for every chunking;
  * ``streaming_mine_frequent`` is the level-synchronous miner on top — a
    shim over the unified driver (``mining/driver.py``) with the
    ``StreamingBackend``, whose per-chunk checkpointing (a
    ``MiningCheckpoint`` records completed levels, the current level's
    itemsets, next chunk, and the partial accumulator) lets a killed mine
    resume MID-LEVEL from the last completed chunk.

Overlap: jax dispatch is async — the ``jax.device_put`` of chunk i+1 is
enqueued before the host blocks on chunk i's compute, double-buffering the
H2D copy against the kernel (the dispatch-level analogue of the in-kernel
DMA pipeline the grid already runs HBM->VMEM).  Ragged last chunks are
zero-padded to the fixed chunk shape (zero-weight rows count nothing), so the
whole sweep reuses one compiled executable.

Exactness bonus: the ``accum='mxu_f32'`` kernel variant requires N < 2^24 per
launch; chunking re-establishes that bound per chunk, making the MXU path
exact for unbounded total N (total per-class counts must still fit the int32
accumulator — guarded at sweep start).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Dict, Hashable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.itemset_count import itemset_counts_into
from .encode import (ItemVocab, class_weights, dedup_rows, encode_bitmap,
                     project_columns)
from .plan import choose_chunk_rows, stream_chunks

Item = Hashable

# The residency threshold where the backend reports no memory limit (CPU).
DEFAULT_STREAM_THRESHOLD_BYTES = 512 << 20


def device_stream_threshold_bytes() -> int:
    """The one source of the dense-vs-streaming residency threshold: an
    encoded DB over it streams from the host.  An eighth of the local
    device's memory where the backend reports a limit, else
    :data:`DEFAULT_STREAM_THRESHOLD_BYTES`.  A tuned table may scale it up
    to twice this (``roofline.autotune.derived_chooser_thresholds``), which
    keeps a resident base under a quarter of the memory: a count launch pads
    and transposes the resident rows and a compaction builds the new base
    beside the old one."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) // 8 if limit else DEFAULT_STREAM_THRESHOLD_BYTES


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    if arr.shape[0] == rows:
        return arr
    pad = np.zeros((rows - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def streaming_counts(
    tx_bits,                      # (N, W) uint32 (host array or device)
    tgt_bits,                     # (K, W) uint32
    weights,                      # (N, C) int32 (or (N,) -> C=1)
    *,
    chunk_rows: Optional[int] = None,
    use_kernel: bool = True,
    accum: Optional[str] = None,
    interpret: Optional[bool] = None,
    block_k: Optional[int] = None,
    block_n: Optional[int] = None,
    init: Optional[np.ndarray] = None,     # (K, C) resume accumulator
    start_chunk: int = 0,
    on_chunk: Optional[Callable[[int, jnp.ndarray], None]] = None,
) -> jnp.ndarray:                 # (K, C) int32
    """Chunked sweep of the counting kernel; bit-identical to one dense pass.

    ``init``/``start_chunk`` resume a partially completed sweep; ``on_chunk``
    is called after each chunk with (chunk_idx, device accumulator) — the
    checkpoint hook (pulling the accumulator to host forces a sync, so only
    pass it when you need durability).  The accumulator is DONATED to the
    next chunk's launch: materialize it inside the callback (np.asarray) —
    holding the array object past the callback reads a deleted buffer on
    accelerator backends.
    """
    tx = np.asarray(tx_bits)
    w = np.asarray(weights)
    if w.ndim == 1:
        w = w[:, None]
    tgt = np.asarray(tgt_bits)
    n = tx.shape[0]
    k, c = tgt.shape[0], w.shape[1]
    if k == 0:
        return jnp.zeros((0, c), jnp.int32)
    # int32 accumulator guard: the largest possible count is the per-class
    # weight-column sum; "unbounded N" holds only while that fits int32
    if n and np.any(w.sum(axis=0, dtype=np.int64) > np.iinfo(np.int32).max):
        raise OverflowError(
            "per-class weight totals exceed int32; streamed counts could "
            "wrap — split the DB or widen the accumulator")
    if chunk_rows is None:
        chunk_rows = choose_chunk_rows(tx.shape[1], c, n_rows=n)
    chunks = stream_chunks(n, chunk_rows)
    acc = (jnp.zeros((k, c), jnp.int32) if init is None
           else jnp.asarray(np.asarray(init), jnp.int32))
    if n == 0 or start_chunk >= len(chunks):
        return acc
    tgt_d = jax.device_put(jnp.asarray(tgt))
    # fixed chunk shape (ragged tail zero-padded): one compiled executable
    pad_to = chunk_rows if len(chunks) > 1 else (chunks[0][1] - chunks[0][0])

    def _prep(j: int):
        s, e = chunks[j]
        return _pad_rows(tx[s:e], pad_to), _pad_rows(w[s:e], pad_to)

    buf = jax.device_put(_prep(start_chunk))
    for j in range(start_chunk, len(chunks)):
        cur_tx, cur_w = buf
        if j + 1 < len(chunks):
            # enqueue next H2D before consuming the current chunk: async
            # dispatch overlaps the copy with this chunk's kernel launches
            buf = jax.device_put(_prep(j + 1))
        acc = itemset_counts_into(
            acc, cur_tx, tgt_d, cur_w, block_k=block_k, block_n=block_n,
            interpret=interpret, use_kernel=use_kernel, accum=accum)
        if on_chunk is not None:
            on_chunk(j, acc)
    return acc


@dataclass
class StreamingDB:
    """Encoded, deduped, class-weighted transaction DB in host-side chunks.

    Mirrors ``DenseDB`` (same encode discipline: support-descending vocab,
    row dedup with per-class weights) but ``bits``/``weights`` stay numpy on
    host and all counting goes through ``streaming_counts``.
    """
    vocab: ItemVocab
    bits: np.ndarray       # (U, W) uint32 unique rows (host)
    weights: np.ndarray    # (U, C) int32 per-class multiplicities (host)
    n_rows: int            # original N (sum of weights)
    n_classes: int
    chunk_rows: int

    @property
    def n_chunks(self) -> int:
        return len(stream_chunks(self.bits.shape[0], self.chunk_rows))

    @property
    def nbytes(self) -> int:
        return int(self.bits.nbytes + self.weights.nbytes)

    @staticmethod
    def encode(
        transactions: Sequence[Sequence[Item]],
        classes: Optional[Sequence[int]] = None,
        n_classes: Optional[int] = None,
        vocab: Optional[ItemVocab] = None,
        min_item_count: int = 1,
        chunk_rows: Optional[int] = None,
    ) -> "StreamingDB":
        if vocab is None:
            vocab = ItemVocab.from_transactions(transactions,
                                                min_count=min_item_count)
        bits = encode_bitmap(transactions, vocab)
        if classes is None:
            w = np.ones((len(transactions), 1), np.int32)
            n_classes = 1
        else:
            n_classes = n_classes or (int(max(classes)) + 1)
            w = class_weights(classes, n_classes)
        ub, uw = dedup_rows(bits, w)
        if chunk_rows is None:
            chunk_rows = choose_chunk_rows(vocab.n_words, n_classes,
                                           n_rows=ub.shape[0])
        return StreamingDB(vocab=vocab, bits=ub, weights=uw,
                           n_rows=len(transactions), n_classes=n_classes,
                           chunk_rows=chunk_rows)

    @staticmethod
    def from_dense(db, chunk_rows: Optional[int] = None) -> "StreamingDB":
        """Host view of a ``DenseDB`` (duck-typed to avoid a module cycle)."""
        bits = np.asarray(db.bits)
        weights = np.asarray(db.weights)
        if chunk_rows is None:
            chunk_rows = choose_chunk_rows(bits.shape[1], weights.shape[1],
                                           n_rows=bits.shape[0])
        return StreamingDB(vocab=db.vocab, bits=bits, weights=weights,
                           n_rows=db.n_rows, n_classes=db.n_classes,
                           chunk_rows=chunk_rows)

    @staticmethod
    def from_arrays(vocab: ItemVocab, bits: np.ndarray, weights: np.ndarray,
                    n_rows: int, n_classes: int,
                    chunk_rows: Optional[int] = None) -> "StreamingDB":
        """Wrap already-encoded/deduped host arrays (serving-store hook)."""
        if chunk_rows is None:
            chunk_rows = choose_chunk_rows(bits.shape[1], weights.shape[1],
                                           n_rows=np.asarray(bits).shape[0])
        return StreamingDB(vocab=vocab, bits=np.asarray(bits),
                           weights=np.asarray(weights), n_rows=n_rows,
                           n_classes=n_classes, chunk_rows=chunk_rows)

    def project(self, keep_items: Sequence[Item]) -> "StreamingDB":
        """Column projection + re-dedup (GFP data reduction, host-side)."""
        proj, sub = project_columns(self.bits, self.vocab, keep_items)
        ub, uw = dedup_rows(proj, self.weights)
        return replace(self, vocab=sub, bits=ub, weights=uw)

    def counts(self, tgt_bits, **kwargs) -> jnp.ndarray:
        kwargs.setdefault("chunk_rows", self.chunk_rows)
        return streaming_counts(self.bits, tgt_bits, self.weights, **kwargs)


# ---------------------------------------------------------------------------
# Level-synchronous mining over a StreamingDB with mid-level checkpointing.
# ---------------------------------------------------------------------------

def streaming_mine_frequent(
    db: StreamingDB,
    min_count: float,
    *,
    class_column: Optional[int] = None,
    max_len: int = 0,
    use_kernel: bool = True,
    accum: Optional[str] = None,
    checkpoint=None,                 # Optional[MiningCheckpoint]
    on_chunk: Optional[Callable[[int, int], None]] = None,
) -> Dict[Tuple[Item, ...], int]:
    """Exact level-synchronous mining, out-of-core, resumable mid-level.

    A shim over the unified driver (``mining/driver.py``) with the
    out-of-core :class:`~repro.mining.backend.StreamingBackend`.  Same
    contract as ``dense_mine_frequent`` (identical result dict).  With a
    ``checkpoint``, progress is durable per chunk: a restart re-loads the
    completed levels, regenerates the interrupted level's candidate list
    (deterministic), and resumes its sweep from the last completed chunk.
    ``on_chunk(level, chunk_idx)`` is a test/progress hook.
    """
    # function-level import: backend.py consumes this module's sweep
    from .backend import StreamingBackend
    from .driver import mine_frequent as _driver_mine

    return _driver_mine(
        StreamingBackend(db, use_kernel=use_kernel, accum=accum), min_count,
        class_column=class_column, max_len=max_len, checkpoint=checkpoint,
        on_chunk=on_chunk)
