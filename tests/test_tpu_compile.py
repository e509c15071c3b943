"""Compiles of the counting kernel for a described TPU v5e — no chip needed.

The TPU compiler is installed beside JAX, so the Mosaic lowering of the
Pallas kernel can be checked here for a chip that is described, not
attached.  Interpret mode runs the kernel body in Python and accepts layouts
and dot operand types that Mosaic refuses; these compiles do not.  Nothing
runs, so they say nothing about results or times: the interpret-mode tests
in ``test_kernel_itemset_count.py`` own exactness.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every pytest
worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.itemset_count import ops
from repro.kernels.itemset_count.kernel import itemset_counts_pallas
from repro.roofline.autotune import ACCUM_LATTICE, BLOCK_K_LATTICE

WIDTHS = (2, 32, 64)
N_ROWS = 4096
N_CLASSES = 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_pallas(sharding, *, n, k, w, c, block_k, block_n, accum):
    """Compile the raw pallas_call at (N, K, W, C) for the described chip;
    returns the compiled program's text."""
    fn = functools.partial(itemset_counts_pallas, block_k=block_k,
                           block_n=block_n, interpret=False, accum=accum)
    compiled = jax.jit(fn).lower(
        _shape((w, n), jnp.uint32, sharding),
        _shape((k, w), jnp.uint32, sharding),
        _shape((c, n), jnp.int32, sharding)).compile()
    return compiled.as_text()


@pytest.mark.parametrize("accum", ACCUM_LATTICE)
@pytest.mark.parametrize("block_k", BLOCK_K_LATTICE)
@pytest.mark.parametrize("w", WIDTHS)
def test_kernel_compiles_for_v5e(one_chip, w, block_k, accum):
    """Every lattice block_k at every width, with K spanning two K-blocks —
    the case where a (C, block_k) output block broke the lane rule."""
    text = _compile_pallas(one_chip, n=N_ROWS, k=2 * block_k, w=w,
                           c=N_CLASSES, block_k=block_k, block_n=1024,
                           accum=accum)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("accum", ACCUM_LATTICE)
@pytest.mark.parametrize("k", (1, 8, 24))
def test_small_k_whole_dimension_compiles(one_chip, k, accum):
    """A small K is padded to a multiple of 8 and launched as one K-block
    that is the whole dimension (ops.itemset_counts shrinks block_k)."""
    k_pad = -(-k // 8) * 8
    text = _compile_pallas(one_chip, n=1024, k=k_pad, w=32, c=1,
                           block_k=k_pad, block_n=1024, accum=accum)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("accum", ACCUM_LATTICE)
@pytest.mark.parametrize("w", WIDTHS)
def test_streaming_step_compiles_for_v5e(one_chip, w, accum):
    """The jitted ``itemset_counts_into`` step (donated accumulator), with
    ``interpret=False`` passed explicitly: ``jax.default_backend()`` here
    still answers the CPU."""
    k, block_k = 300, 64
    step = ops._counts_into_jit(True)
    compiled = step.lower(
        _shape((k, N_CLASSES), jnp.int32, one_chip),
        _shape((N_ROWS, w), jnp.uint32, one_chip),
        _shape((k, w), jnp.uint32, one_chip),
        _shape((N_ROWS, N_CLASSES), jnp.int32, one_chip),
        block_k=block_k, block_n=1024, interpret=False, use_kernel=True,
        accum=accum).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serve_flush_geometry_compiles(one_chip):
    """The resident geometry a real deployment serves: 4M rows over 1,024
    items (W = 32), two classes, one 256-key flush."""
    n = 4_000_768                      # 4,000,000 rows padded to block_n
    text = _compile_pallas(one_chip, n=n, k=256, w=32, c=N_CLASSES,
                           block_k=256, block_n=1024, accum="vpu_int32")
    assert "tpu_custom_call" in text


def test_mesh_count_compiles_for_four_chips(topo, monkeypatch):
    """The sharded store's one-launch flush (``resident_distributed_counts``):
    a shard_map over a 4-chip data axis, the kernel on each chip's rows and
    one psum.  Interpret mode is switched off in the test, since the trace
    asks the CPU default backend."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.mining.distributed import _count_shard_fn

    monkeypatch.setattr(ops, "_interpret", lambda interpret: False)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rows = NamedSharding(mesh, P(("data",), None))
    whole = NamedSharding(mesh, P(None, None))
    n = 4_000_000
    fn = _count_shard_fn(mesh, ("data",), None, True, 256, 1024,
                         "vpu_int32")
    text = fn.lower(_shape((n, 32), jnp.uint32, rows),
                    _shape((256, 32), jnp.uint32, whole),
                    _shape((n, N_CLASSES), jnp.int32, rows)).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
