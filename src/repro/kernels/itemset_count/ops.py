"""Public jit'd wrapper around the itemset-counting Pallas kernel.

Handles padding, layout transposition, backend selection (interpret mode on
CPU — the kernel body executes in Python for correctness validation; compiled
Mosaic on every accelerator), and a pure-jnp fallback for degenerate shapes.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...roofline import autotune
from ...roofline.kernel_model import count_launch, record_launch
from .kernel import itemset_counts_pallas
from .ref import itemset_counts_ref, itemset_counts_ref_blocked

__all__ = ["itemset_counts", "itemset_counts_into", "itemset_counts_ref",
           "itemset_counts_ref_blocked", "mxu_f32_exact", "checked_accum",
           "weight_sum_bound"]

# Unrolling the word loop beyond this is counter-productive; fall back to the
# blocked jnp reference (still jit-compiled) for enormous item universes.
MAX_KERNEL_WORDS = 64

# mxu_f32 accumulates each launch's per-class weight sums in f32, which holds
# integers exactly only below 2^24.
MXU_F32_MAX_WEIGHT_SUM = 1 << 24


def _interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode on the CPU backend only: an accelerator always
    runs the compiled kernel."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(f"interpret=True on the {jax.default_backend()} "
                         "backend: the kernel runs compiled there")
    return interpret


def weight_sum_bound(weights) -> int:
    """The largest per-class |weight| sum of ``weights`` (N, C), read on the
    host: the bound a caller that owns the weights computes once and passes
    to every launch as ``weight_bound``."""
    w = np.asarray(weights)
    if w.size == 0:
        return 0
    if w.ndim == 1:
        w = w[:, None]
    return int(np.abs(w.astype(np.int64)).sum(axis=0).max())


def mxu_f32_exact(weights, weight_bound: Optional[int] = None) -> bool:
    """True when an ``accum='mxu_f32'`` launch over ``weights`` (N, C) is
    exact: every class's |weight| sum stays below 2^24, which bounds every
    f32 partial of the launch.  ``weight_bound`` is the caller's known upper
    bound on those sums (a store's class totals); without it the weights
    are read on the host."""
    if weight_bound is None:
        weight_bound = weight_sum_bound(weights)
    return int(weight_bound) < MXU_F32_MAX_WEIGHT_SUM


def checked_accum(requested: Optional[str], resolved: str, weights,
                  weight_bound: Optional[int] = None) -> str:
    """The accumulator a launch may use.  ``mxu_f32`` needs the weight-sum
    bound: a tuned (resolved) pick that breaks it falls back to the exact
    VPU path, an explicit request raises.  Under a jit trace the weights
    are abstract — the callers that trace (``itemset_counts_into``, the
    mesh launch) check the bound eagerly before tracing."""
    if resolved != "mxu_f32" or (weight_bound is None and isinstance(
            weights, jax.core.Tracer)) or mxu_f32_exact(weights, weight_bound):
        return resolved
    if requested == "mxu_f32":
        raise ValueError(
            "mxu_f32 accumulation is exact only while each class's weight "
            f"sum per launch is < 2^24; weights of shape {tuple(weights.shape)} "
            "exceed it — chunk the sweep (mining/stream.py) or use "
            "accum='vpu_int32'")
    return autotune.DEFAULT_ACCUM


def itemset_counts(
    tx_bits: jnp.ndarray,     # (N, W) uint32
    tgt_bits: jnp.ndarray,    # (K, W) uint32
    weights: jnp.ndarray,     # (N, C) int32  (or (N,) -> C=1)
    *,
    block_k: Optional[int] = None,
    block_n: Optional[int] = None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
    accum: Optional[str] = None,
    weight_bound: Optional[int] = None,
) -> jnp.ndarray:             # (K, C) int32
    """Exact counts of every target itemset, per weight column (class).

    ``block_k`` / ``block_n`` / ``accum`` left as None resolve through the
    active per-device tuning table (``roofline.autotune``), falling back to
    the compiled-in defaults — callers pin explicit values to bypass it.

    ``accum='mxu_f32'`` routes the weighted reduction through the MXU in f32
    (exact while each class's weight sum per launch is < 2^24; enforced
    below) — the counting-kernel §Perf variant.  A caller that knows an
    upper bound on every class's weight sum passes it as ``weight_bound``,
    so the check never copies device-resident weights to the host."""
    if weights.ndim == 1:
        weights = weights[:, None]
    n, w = tx_bits.shape
    k = tgt_bits.shape[0]
    c = weights.shape[1]
    # checked before any route: interpret=True never passes on a chip, not
    # even where the jnp reference below answers instead of the kernel
    interpret = _interpret(interpret)
    if k == 0:
        return jnp.zeros((0, c), jnp.int32)
    if n == 0:
        return jnp.zeros((k, c), jnp.int32)
    if not use_kernel or w > MAX_KERNEL_WORDS:
        return itemset_counts_ref_blocked(tx_bits, tgt_bits, weights)

    # Per-launch telemetry: the call's parts as child spans of
    # ``kernel.count``, and wall time vs the roofline model's prediction for
    # this geometry (repro.obs / roofline.kernel_model).  Only measurable at
    # the eager boundary — under a jit trace (e.g. the streaming
    # itemset_counts_into step) the operands are Tracers and host timing
    # would clock trace time, not the launch, so recording is skipped there.
    eager = (not isinstance(tx_bits, jax.core.Tracer)
             and not isinstance(tgt_bits, jax.core.Tracer))
    timed = obs.kernel_timing_enabled() and eager
    span = obs.TRACER.span if eager else _no_span
    with span("kernel.count", {"n": n, "k": k, "w": w, "c": c}):
        with span("kernel.prepare"):
            requested = accum
            if block_k is None or block_n is None or accum is None:
                # Eager host-side resolution (n/k/w/c are concrete Python
                # ints even under a jit trace) so any jit cache downstream
                # keys on the CONCRETE tuned values — never on a None that
                # could alias across table swaps.
                cfg = autotune.resolve_launch_config(n, k, w, c)
                block_k = cfg.block_k if block_k is None else block_k
                block_n = cfg.block_n if block_n is None else block_n
                accum = cfg.accum if accum is None else accum
            accum = checked_accum(requested, accum, weights, weight_bound)

            # Shrink blocks for small problems, keeping TPU-friendly minima.
            block_n = min(block_n, _round_up(n, 128))
            block_k = min(block_k, _round_up(k, 8))

            n_pad = _round_up(n, block_n) - n
            k_pad = _round_up(k, block_k) - k
            # pad rows get weight 0; pad targets are sliced off
            tx_p = jnp.pad(tx_bits, ((0, n_pad), (0, 0)))
            wt_p = jnp.pad(weights, ((0, n_pad), (0, 0)))
            tgt_p = jnp.pad(tgt_bits, ((0, k_pad), (0, 0)))
            tx_t = tx_p.T
            wt_t = wt_p.T.astype(jnp.int32)
        with span("kernel.launch"):
            t0 = time.perf_counter() if timed else 0.0
            out = itemset_counts_pallas(
                tx_t, tgt_p, wt_t,
                block_k=block_k, block_n=block_n, interpret=interpret,
                accum=accum,
            )                                             # (K_pad, C)
        if timed:
            # blocking gives a TRUE wall time; free on CPU (callers
            # materialize the counts immediately) but serializes a pipelined
            # TPU launch stream — obs.configure(kernel_timing=False) when
            # overlap matters
            with span("kernel.wait"):
                out.block_until_ready()
            elapsed = time.perf_counter() - t0
        if eager:
            # every eager launch is counted; its time only when timed
            with span("kernel.record"):
                if timed:
                    record_launch(n, k, w, c, elapsed)
                else:
                    count_launch(n, k, w, c)
    return out[:k, :]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _no_span(name: str, attrs: Optional[dict] = None):
    """The tracer's disabled span, for the parts of a launch under a jit
    trace: nothing is recorded there."""
    return obs.tracing.NOOP_SPAN


# ---------------------------------------------------------------------------
# Process telemetry that needs JAX (``repro.obs`` imports only the stdlib):
# the program's spans on a recorded profile's host plane, and a count of the
# compilations JAX makes, each a ``jax.compile`` span under the span open on
# the compiling thread while tracing is on.
# ---------------------------------------------------------------------------

_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_M_COMPILES = {stage: obs.REGISTRY.counter("jax_compiles_total", stage=stage)
               for stage in _COMPILE_STAGES.values()}


def _on_compile_event(event: str, seconds: float, **_kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    _M_COMPILES[stage].inc()
    if obs.TRACER.enabled:
        obs.TRACER.retro("jax.compile", seconds, {"stage": stage})


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
obs.tracing.set_host_annotation(jax.profiler.TraceAnnotation)


# ---------------------------------------------------------------------------
# Streaming accumulation step.  The out-of-core sweep (mining/stream.py) keeps
# the small (K, C) count block device-resident and adds one chunk's counts per
# call; donating the accumulator lets the compiler update it in place, so a
# sweep allocates O(chunk) device memory regardless of total N.  Note the
# mxu_f32 exactness bound (weight sum < 2^24) then applies PER CHUNK —
# chunking makes the MXU variant exact for unbounded N.
# ---------------------------------------------------------------------------

def _counts_into(acc, tx_bits, tgt_bits, weights, *, block_k, block_n,
                 interpret, use_kernel, accum):
    return acc + itemset_counts(
        tx_bits, tgt_bits, weights, block_k=block_k, block_n=block_n,
        interpret=interpret, use_kernel=use_kernel, accum=accum)


@functools.lru_cache(maxsize=None)
def _counts_into_jit(donate: bool):
    kwargs = dict(static_argnames=("block_k", "block_n", "interpret",
                                   "use_kernel", "accum"))
    if donate:
        kwargs["donate_argnums"] = (0,)
    return jax.jit(_counts_into, **kwargs)


def itemset_counts_into(
    acc: jnp.ndarray,             # (K, C) int32 running counts (donated)
    tx_bits: jnp.ndarray,         # (N_chunk, W) uint32
    tgt_bits: jnp.ndarray,        # (K, W) uint32
    weights: jnp.ndarray,         # (N_chunk, C) int32
    *,
    block_k: Optional[int] = None,
    block_n: Optional[int] = None,
    interpret: Optional[bool] = None,
    use_kernel: bool = True,
    accum: Optional[str] = None,
) -> jnp.ndarray:                 # (K, C) int32 = acc + chunk counts
    """``acc + itemset_counts(chunk)`` fused in one jit; acc stays on device.

    Launch config and interpret mode resolve EAGERLY here (not inside the
    trace): the jit cache is keyed on the static block/accum values, so a
    table swap between calls must surface as different statics, not a
    stale cached trace — and the mxu_f32 weight bound can only be checked
    on concrete weights."""
    requested = accum
    if block_k is None or block_n is None or accum is None:
        wts = weights if weights.ndim == 2 else weights[:, None]
        cfg = autotune.resolve_launch_config(
            tx_bits.shape[0], tgt_bits.shape[0], tx_bits.shape[1],
            wts.shape[1])
        block_k = cfg.block_k if block_k is None else block_k
        block_n = cfg.block_n if block_n is None else block_n
        accum = cfg.accum if accum is None else accum
    accum = checked_accum(requested, accum, weights)
    interpret = _interpret(interpret)
    donate = jax.default_backend() != "cpu"  # CPU donation warns, no-op
    return _counts_into_jit(donate)(
        acc, tx_bits, tgt_bits, weights, block_k=block_k, block_n=block_n,
        interpret=interpret, use_kernel=use_kernel, accum=accum)
