"""Pipeline parallelism: GPipe-style microbatch schedule over a 'stage' mesh
axis, built on shard_map + lax.ppermute.

The framework's depth scaling is primarily scan-over-layers + FSDP/TP, but at
1000+ nodes a pipeline axis is the standard third dimension (cuts the FSDP
all-gather span and the TP collective domain).  This module provides the
composable stage executor; `tests/test_pipeline.py` proves numerical
equivalence with sequential execution on a multi-device host mesh.

Schedule (forward): T = M + S - 1 ticks for M microbatches over S stages.
At tick t, stage s computes microbatch (t - s) (a bubble otherwise), then the
activations rotate one hop with a single collective-permute — the classic
GPipe pipeline with an S-1-tick fill/drain bubble; utilization M/(M+S-1).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_forward(
    stage_params: Any,          # pytree, leaves stacked on a leading S dim
    x: jax.Array,               # (M, mb, ...) microbatched inputs
    body: Callable[[Any, jax.Array], jax.Array],   # one stage's computation
    mesh: Mesh,
    stage_axis: str = "stage",
    batch_axis: str = "data",
) -> jax.Array:                 # (M, mb, ...) outputs of the final stage
    """Run `body` S times over x as an S-stage pipeline."""
    n_stages = mesh.shape[stage_axis]
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1

    param_specs = jax.tree.map(lambda _: P(stage_axis), stage_params)
    x_spec = P(None, batch_axis)
    out_spec = P(None, batch_axis)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_specs, x_spec), out_specs=out_spec,
        check_vma=False,
    )
    def run(local_params, xs):
        # local_params leaves have leading dim 1 (this stage's slice)
        my_params = jax.tree.map(lambda a: a[0], local_params)
        sid = jax.lax.axis_index(stage_axis)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        zero = jnp.zeros_like(xs[0])

        def tick(carry, t):
            ring_in = carry
            # stage 0 ingests microbatch t (when valid); others take the ring
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            feed = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
            inp = jnp.where(sid == 0, feed, ring_in)
            out = body(my_params, inp)
            ring_out = jax.lax.ppermute(out, stage_axis, perm)
            # final stage emits microbatch (t - S + 1) at this tick
            return ring_out, out

        _, outs = jax.lax.scan(tick, zero, jnp.arange(ticks))  # (T, mb, ...)
        # keep the last-stage outputs for ticks S-1 .. T-1, i.e. microbatches
        # 0..M-1; on non-final stages this value is discarded by the psum mask
        valid = outs[n_stages - 1:]
        is_last = (sid == n_stages - 1).astype(valid.dtype)
        # every stage returns its slice; only the final stage's is nonzero,
        # and the stage axis is contracted by summing (one nonzero term)
        return jax.lax.psum(valid * is_last, stage_axis)

    return run(stage_params, x)


def split_stages(params_stacked: Any, n_stages: int) -> Any:
    """(L, ...) layer-stacked params -> (S, L/S, ...) stage-stacked."""
    def r(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])
    return jax.tree.map(r, params_stacked)
