"""Pallas TPU kernel: multitude-targeted itemset counting.

TPU mapping of the GFP-growth counting step (see ref.py for semantics).

Layout rationale (TPU memory hierarchy):
  * transactions arrive TRANSPOSED as (W, N): the huge N axis is the 128-lane
    dimension, W (a handful of packed uint32 words) is the sublane axis;
  * targets stay (K, W): K is the sublane axis of the (K_b, N_b) containment
    tile that feeds the reduction;
  * weights arrive (C, N), lane-dense along N like the transactions;
  * the output is (K, C): its block is (K_b, C) with C the whole last
    dimension, so every K_b that is a multiple of 8 lowers — a (C, K_b)
    output block would need K_b % 128 == 0 or K_b == K;
  * grid = (K_tiles, N_tiles), N fastest-varying; the (K_b, C) output block
    is revisited across the N sweep and accumulated in place (zeroed when
    n_idx == 0) — VMEM-resident accumulator, one HBM writeback per K tile;
  * inside a grid step a loop walks the K_b targets in sub-tiles of at most
    32 rows.  The sub-tile bounds the live containment tile whatever the
    block sizes: a whole (K_b, N_b) tile at K_b=512, W=64 overran the 16 MiB
    scoped VMEM, and compiled ten times slower where it fit;
  * containment is a residue over the W packed words (W static and small,
    32·W items; the loop is unrolled): a row t holds target g iff
    ``t | ~g`` is all ones in every word.  ``~g`` is taken once on the
    (sub, W) target sub-tile; each word then costs one OR and one AND per
    (sub, N_b) vreg on the VPU, and one compare against 0xFFFFFFFF follows
    the word loop: W ORs, W - 1 ANDs and a compare, 2W ALU ops per vreg,
    where ``(t & g) == g`` folded with a mask AND cost 3W - 1.  On a v5e
    the loop is bound by vector-ALU issue, so this count sets the kernel's
    pace;
  * every word of every target is tested for every row, zero words and
    all: no word skipping, no early exit, no containment on the MXU.  The
    benchmark's roofline prices exactly this dense VPU work
    (``n·k·(2w + c)`` ops), so a kernel that did less of it would read
    above its own roofline.

VMEM budget per grid step (W<=64, N_b=1024, K_b<=512, C<=8):
  tx (64,1024)·4B = 256KiB ; tgt (512,64)·4B = 128KiB ; w (8,1024)·4B = 32KiB ;
  ~tgt sub-tile (32,64)·4B = 8KiB ; residue sub-tile (32,1024)·4B = 128KiB ;
  out (512,8)·4B = 16KiB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Targets per containment sub-tile (the loop inside one grid step).
SUB_K = 32


def _itemset_count_kernel(tx_ref, tgt_ref, w_ref, out_ref, *, n_words: int,
                          accum: str = "vpu_int32"):
    """Grid step (k_idx, n_idx): accumulate counts for one (K_b, N_b) tile.

    ``accum``:
      * 'vpu_int32' — per class, a masked int32 lane sum on the VPU: exact
        for every weight whose class total fits int32 (the store's guard).
        The TPU MXU takes no int32 operands, so this path has no dot;
      * 'mxu_f32'   — f32 dot on the MXU at ``Precision.HIGHEST`` (the
        operands are not rounded to bf16): exact while each class's weight
        sum over the launch stays below 2^24 (checked in ops.py).
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    weights = w_ref[...]                               # (C, N_b) int32
    block_k = tgt_ref.shape[0]
    sub = math.gcd(block_k, SUB_K)                     # K_b % 8 == 0

    def sub_tile(i, carry):
        r0 = pl.multiple_of(i * sub, sub)
        # A row holds target g iff t | ~g is all ones in every word: one
        # OR and one AND per word, one compare after the unrolled loop.
        ng = ~tgt_ref[pl.ds(r0, sub), :]               # (sub, W) uint32
        res = None
        for w in range(n_words):
            t_row = tx_ref[w, :]                       # (N_b,) uint32
            word = t_row[None, :] | ng[:, w][:, None]  # (sub, N_b) uint32
            res = word if res is None else (res & word)
        acc = res == jnp.uint32(0xFFFFFFFF)            # (sub, N_b) bool
        if accum == "mxu_f32":
            # (sub, N_b) x (C, N_b) -> (sub, C), contracting the lane axis
            part = jax.lax.dot_general(
                acc.astype(jnp.float32), weights.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)
        else:
            cols = [jnp.sum(jnp.where(acc, weights[c:c + 1, :], 0), axis=1,
                            keepdims=True)             # (sub, 1) int32
                    for c in range(weights.shape[0])]
            part = cols[0] if len(cols) == 1 else jnp.concatenate(cols,
                                                                  axis=1)
        out_ref[pl.ds(r0, sub), :] += part
        return carry

    jax.lax.fori_loop(0, block_k // sub, sub_tile, 0)


@functools.partial(jax.jit, static_argnames=("block_k", "block_n", "interpret",
                                              "accum"))
def itemset_counts_pallas(
    tx_bits_t: jnp.ndarray,   # (W, N) uint32, N % block_n == 0
    tgt_bits: jnp.ndarray,    # (K, W) uint32, K % block_k == 0
    weights_t: jnp.ndarray,   # (C, N) int32
    *,
    block_k: int = 256,
    block_n: int = 1024,
    interpret: bool = False,
    accum: str = "vpu_int32",
) -> jnp.ndarray:             # (K, C) int32
    n_words, n = tx_bits_t.shape
    k = tgt_bits.shape[0]
    c = weights_t.shape[0]
    if n % block_n or k % block_k:
        raise ValueError(f"N({n}) % block_n({block_n}) and K({k}) % "
                         f"block_k({block_k}) must be 0 (pad in ops.py)")

    grid = (k // block_k, n // block_n)
    kernel = functools.partial(_itemset_count_kernel, n_words=n_words,
                               accum=accum)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_words, block_n), lambda ki, ni: (0, ni)),
            pl.BlockSpec((block_k, n_words), lambda ki, ni: (ki, 0)),
            pl.BlockSpec((c, block_n), lambda ki, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_k, c), lambda ki, ni: (ki, 0)),
        out_shape=jax.ShapeDtypeStruct((k, c), jnp.int32),
        interpret=interpret,
        name="itemset_count",
    )(tx_bits_t, tgt_bits, weights_t)
